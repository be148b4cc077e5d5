"""The benchmark's files for latent attention with routed and shared experts
(moonlight-16b-a3b) at a tiny width on the CPU: the reference's weights are
the program's, the cell's driver serves a tiny copy `correct` and records
the routed counts of each decode step, a planted altered token and a
decode step that returns its cache unchanged fail the checks, the float8
control fails the mean gap's limit, the counts add up by hand, and the
roofline reads a hand-made trace."""
from __future__ import annotations

import copy
import time

import jax
import numpy as np
import pytest
from conftest import ROOT, TINY_TRAFFIC

from chipbench import counts, counts_mla_moe, harness, peaks, spec
from chipbench import trace as tr
from chipbench.drivers import serve_closed as sc
from chipbench.drivers import serve_closed_mla_moe as smm
from chipbench.reference import mla_moe_lm

NAME = "moonlight-16b-a3b.long-decode-32"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
KIND = "TPU v5 lite"
SEED = 2**33 + 11
# The widest logit gap of served tokens at this width: the program read 0
# to 0.36 over seeds 0-11 (above 0.02 only where a held expert and another
# swap places at the router's sixth choice, as bf16 activations move its
# scores), the float8 control 0.25 to 1.12. The limit catches a wrong
# token (gaps of whole units) and not one such swap. The mean gap over the
# same seeds: the program 0 to 0.0114, the control 0.0230 to 0.152. The
# cell's own limits are set from chip readings at its size.
TINY_LIMITS = {"max_logit_gap": 0.5, "mean_logit_gap": 0.017}


def tiny_cell(**traffic) -> spec.Cell:
    """The cell at a width the CPU serves fast: 16 published experts, of
    which experts 4-7 are held, top-3."""
    cell = copy.deepcopy(spec.cell(NAME, ROOT))
    cell.config.update(
        hidden_size=64, intermediate_size=128, num_hidden_layers=3,
        num_attention_heads=4, vocab_size=256, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        moe_intermediate_size=32, n_routed_experts=4, num_experts_per_tok=3)
    cell.config["deployment"] = dict(cell.config["deployment"],
                                     published_n_routed_experts=16,
                                     held_experts=[4, 8])
    cell.traffic.update(TINY_TRAFFIC, **traffic)
    cell.limits = dict(TINY_LIMITS)
    return cell


def _execute(cell, seed=7):
    # the window's first step prefills every slot eagerly, each lowering two
    # layer scans (dense, then MoE): on a loaded CPU that takes seconds
    return harness.execute(cell, seed=seed, seconds=15.0, trace=False,
                           t_process=time.perf_counter(), device=CPU)


def test_weights_are_the_programs():
    from repro.launch.serve import init_params
    conf = tiny_cell().config
    prog = init_params(smm.program_config(conf), SEED)
    ref = mla_moe_lm.init_weights(conf, SEED)
    pairs = [(ref["emb"], prog["emb"]["table"]),
             (ref["head"], prog["unembed"]["w"]),
             (ref["final_norm"], prog["final_norm"]["scale"]),
             (ref["dense_layers"]["mlp"]["wg"],
              prog["dense_blocks"]["mlp"]["wg"]["w"])]
    for mine, theirs in (("dense_layers", "dense_blocks"),
                         ("moe_layers", "blocks")):
        for k in ("wq", "wkv_a", "wkv_b", "wo"):
            pairs.append((ref[mine]["attn"][k],
                          prog[theirs]["attn"][k]["w"]))
        pairs.append((ref[mine]["attn"]["kv_norm"],
                      prog[theirs]["attn"]["kv_norm"]["scale"]))
    moe = prog["blocks"]["moe"]
    pairs += [(ref["moe_layers"]["router"], moe["router"]),
              (ref["moe_layers"]["router_bias"], moe["router_bias"])]
    pairs += [(ref["moe_layers"]["experts"][k], moe[k])
              for k in ("wg", "wu", "wd")]
    pairs += [(ref["moe_layers"]["shared"][k], moe["shared"][k]["w"])
              for k in ("wg", "wu", "wd")]
    for a, b in pairs:
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert len(jax.tree.leaves(ref)) == len(jax.tree.leaves(prog))


def test_a_tiny_copy_of_the_cell_is_served_correct():
    cell = tiny_cell(clients=2, slots=2)
    res = _execute(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    tr_ = cell.traffic
    assert res["checks"]["sampled_tokens"]["value"] >= \
        tr_["check_per_slot"] * tr_["slots"] * tr_["max_new"]


def test_each_decode_step_of_the_window_has_its_routed_counts():
    cell = tiny_cell(max_new=16, max_seq=32)
    run = smm.run(cell, seed=2**31 + 3, seconds=0.5)
    steps, routed = run.records["window_steps"], run.records["routed"]
    assert len(steps) == len(routed) > 0
    k = cell.config["num_experts_per_tok"]
    for st, r in zip(steps, routed):
        assert (r is None) == (not st.contexts)
        if r is not None:
            assert r.shape == (2, 4)         # MoE layers, held experts
            assert r.sum(axis=1).max() <= cell.traffic["slots"] * k
    assert all(c.ok for c in run.checks), run.checks


def _altered_token(make):
    def patched(cfg):
        step = make(cfg)

        def serve_step(params, cache, tokens):
            tok, *rest = step(params, cache, tokens)
            return (tok.at[0, 0].set((tok[0, 0] + 1) % cfg.vocab_size),
                    *rest)
        return serve_step
    return patched


def _state_unchanged(make):
    def patched(cfg):
        step = make(cfg)

        def serve_step(params, cache, tokens):
            tok, _, *rest = step(params, cache, tokens)
            return (tok, cache, *rest)
        return serve_step
    return patched


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token-altered", "state-unchanged"])
def test_a_broken_decode_step_is_not_correct(fault, monkeypatch):
    from repro.serve import decode
    monkeypatch.setattr(decode, "make_serve_step",
                        fault(decode.make_serve_step))
    run = smm.run(tiny_cell(clients=2, slots=2), seed=2**32 + 7, seconds=0.0)
    checks = {c.name: c for c in run.checks}
    assert checks["sampled_tokens"].value > 0
    assert not checks["max_logit_gap"].ok, checks


@pytest.mark.parametrize("seed", [5, 6])
def test_the_float8_control_is_not_correct(seed):
    """At the float8 control's tokens the mean gap is over its limit, at
    the program's within it (the widest gap need not tell them apart)."""
    cell = tiny_cell()
    eng = smm.build(cell, seed)
    loop, _, _ = sc.serve(eng, cell.traffic, seed, 0.0)
    sc.drain(loop)
    picked = sc.sample(loop, seed, 1)
    gaps, ctl = smm.reference_gaps(cell.config, seed, picked, 2,
                                   control=mla_moe_lm.fp8_linear)
    assert gaps.size == ctl.size == 4 * 8
    program, control = smm.gap_readings(gaps), smm.gap_readings(ctl)
    assert all(v <= TINY_LIMITS[k] for k, v in program.items()), program
    assert control["mean_logit_gap"] > TINY_LIMITS["mean_logit_gap"]
    assert control["max_logit_gap"] > program["max_logit_gap"]


def test_hand_counts_at_tiny_width():
    conf = tiny_cell().config
    d, h, r, nope, rope, v, L, V = 64, 4, 32, 16, 8, 16, 3, 256
    attn = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) \
        + h * v * d
    expert = 3 * d * 32
    moe = 4 * expert + 2 * expert + d * 16        # held, shared, router
    assert counts_mla_moe.matmul_params(conf) == \
        L * attn + 3 * d * 128 + 2 * moe + 2 * V * d
    norms = L * (2 * d + r) + d
    assert counts_mla_moe.weight_count(conf) == \
        counts_mla_moe.matmul_params(conf) + norms + 2 * 16
    assert counts_mla_moe.latent_bytes_per_token(conf) == L * (r + rope) * 2
    # two slots at contexts 5 and 7; layer 1's experts got 3 and 0 pairs,
    # 2 and 1; layer 2's 0, 0, 4, 2: five experts read
    routed = [[3, 0, 2, 1], [0, 0, 4, 2]]

    def tok(c):
        return L * 2 * (d * h * (nope + rope) + d * (r + rope) + h * nope * r
                        + h * (r + rope) * c + h * r * c + h * r * v
                        + h * v * d) \
            + 2 * 3 * d * 128 + 2 * 2 * (2 * expert + d * 16) + 2 * d * V
    assert counts_mla_moe.decode_flops(conf, [5, 7], routed) == \
        tok(5) + tok(7) + 2 * expert * 12
    weights = counts_mla_moe.weight_count(conf) - V * d - 2 * 4 * expert \
        + 2 * d + 5 * expert
    assert counts_mla_moe.decode_bytes(conf, [5, 7], routed) == \
        2 * weights + L * (r + rope) * 2 * (12 + 2)


def _run_with_trace(routed):
    """Two decode steps in a window [0, 10) ms, at 4 and 6 ms of device
    time, the second with no routed counts recorded after it (a step past
    the window's end does not count)."""
    cell = tiny_cell()
    P = cell.traffic["prompt_len"]
    steps = [sc.Step(0.0, 2.0, 0, [P + 1, P + 1]),
             sc.Step(2.0, 3.0, 0, [P + 2, P + 2]),
             sc.Step(9.0, 11.0, 0, [P + 3, P + 3])]
    run = harness.Run(cell=cell, seconds=10.0, setup_s=1.0, attempted=2,
                      failed=0, checks=[], memory_peak_bytes=1,
                      records={"sent": [], "t_start": 0.0, "t_end": 10.0,
                               "compiles": [], "window_steps": steps,
                               "routed": routed},
                      device_kind=KIND)
    t, ms = tr.Trace(), 1e6
    t.devices["/device:TPU:0"] = {
        tr.MODULES: [tr.Event("jit_serve_step(3)", 1 * ms, 4 * ms),
                     tr.Event("jit_serve_step(3)", 10 * ms, 6 * ms)],
        tr.OPS: [tr.Event("fusion", 1 * ms, 4 * ms)]}
    run.trace, run.trace_lo, run.trace_hi = t, 0.0, 40 * ms
    return run, P


def test_the_roofline_reads_a_hand_made_trace():
    a, b = np.array([[3, 0], [1, 2]]), np.array([[0, 0], [6, 0]])
    run, P = _run_with_trace([a, b, a])
    conf, pk = run.cell.config, peaks.peak(KIND)
    least = [counts.least_time_s(counts_mla_moe.decode_flops(conf, c, r),
                                 counts_mla_moe.decode_bytes(conf, c, r),
                                 pk)[0]
             for c, r in (([P + 1] * 2, a), ([P + 2] * 2, b))]
    read = spec.reader("mla_moe_decode_roofline", ROOT)
    assert read(run) == pytest.approx(100 * (sum(least) / 2) / 5e-3)
    assert least[1] < least[0]          # fewer experts read
    run.records.pop("routed")           # a program without the counter
    assert read(run) is None
