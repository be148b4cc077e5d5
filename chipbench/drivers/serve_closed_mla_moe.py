"""The closed loop of ``serve_closed`` for a configuration of latent
attention with routed and shared experts (``model_type`` ``deepseek_v3``).

It runs ``serve_closed``'s loop, window, drain and sample unchanged. It
differs in the reference it compares with (``reference/mla_moe_lm.py``),
in one check more (``mean_logit_gap``, see :func:`gap_readings`),
in the sizes it hands the program (the latent and expert widths, and the
experts this chip holds of the deployment the file states), and in one
record: the engine's routed counts of each decode step of the window
(``records["routed"]``, beside ``records["window_steps"]``; None for a step
that ran no decode), which ``metrics/mla_moe_decode_roofline.py`` reads.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, trace
from chipbench.drivers import serve_closed as sc
from chipbench.reference import mla_moe_lm

# Configuration file keys -> the program's ModelConfig fields, beside
# serve_closed's.
_SIZES = {"kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim",
          "v_head_dim": "v_head_dim",
          "moe_intermediate_size": "moe_d_ff",
          "n_shared_experts": "num_shared_experts",
          "num_experts_per_tok": "experts_per_token",
          "first_k_dense_replace": "first_dense_layers",
          "routed_scaling_factor": "routed_scaling",
          "n_routed_experts": "experts_held"}


def program_config(conf):
    """The program's ModelConfig for ``conf``: serve_closed's, with the
    latent and expert sizes, and the router over the deployment's
    published experts of which this chip holds ``n_routed_experts`` from
    ``deployment.held_experts[0]`` on."""
    dep = conf["deployment"]
    return dataclasses.replace(
        sc.program_config(conf),
        num_experts=dep["published_n_routed_experts"],
        first_expert=dep["held_experts"][0],
        **{f: conf[k] for k, f in _SIZES.items()})


def build(cell, seed: int):
    """Weights, engine and one warm request through it."""
    from repro.launch.serve import init_params
    from repro.serve.engine import Request, ServeEngine
    cfg = program_config(cell.config)
    tr = cell.traffic
    params = init_params(cfg, seed)
    eng = ServeEngine(cfg, params, slots=tr["slots"], max_seq=tr["max_seq"])
    warm = Request(rid=-1, prompt=sc.prompt(seed, tr["clients"], 0,
                                            tr["prompt_len"], cfg.vocab_size),
                   max_new=2)
    eng.submit(warm)
    while not warm.done:
        eng.step()
    eng.finished.clear()
    eng.routed.clear()
    return eng


def reference_gaps(conf, seed, picked: List[sc.Sent], block: int,
                   control=None):
    """``serve_closed.reference_gaps`` against this configuration's
    reference."""
    weights = mla_moe_lm.init_weights(conf, seed)
    gaps, ctl = [], []
    for i in range(0, len(picked), block):
        chunk = picked[i:i + block]
        P = len(chunk[0].req.prompt)
        n = len(chunk[0].req.out)
        toks = np.stack([np.concatenate([s.req.prompt, s.req.out[:-1]])
                         for s in chunk]).astype(np.int32)
        served = np.stack([s.req.out for s in chunk]).astype(np.int32)
        pos = np.broadcast_to(np.arange(P - 1, P - 1 + n), served.shape)
        ref = mla_moe_lm.logits(conf, weights, toks, pos)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, jnp.asarray(served)[..., None],
                                  axis=-1)[..., 0]
        gaps.append(np.asarray(best - got).ravel())
        if control is not None:
            low = mla_moe_lm.logits(conf, weights, toks, pos, linear=control)
            pick = jnp.argmax(low, axis=-1)
            got = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
            ctl.append(np.asarray(best - got).ravel())
            del low
        del ref
    return (np.concatenate(gaps),
            np.concatenate(ctl) if control is not None else None)


def gap_readings(gaps: np.ndarray) -> dict:
    """The checks read from the sampled tokens' gaps: the widest (a wrong
    token), and the mean (the token choice a lower precision blurs). A
    routed model's widest gap is wide even in bfloat16, where a held
    expert and another swap places at the router's last choice, and so it
    cannot tell bfloat16 from float8; the mean, nearly all zeros and a
    few such swaps in the program's, can."""
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


def routed_by_step(loop: sc.Loop, counts: List[np.ndarray]) -> List:
    """``counts``, the engine's one array per decode step, beside the
    loop's steps: a step ran one decode exactly when a decode token came
    in it (``Step.contexts``)."""
    it = iter(counts)
    out = [next(it) if st.contexts else None for st in loop.steps]
    if next(it, None) is not None:
        raise RuntimeError("more routed counts than decode steps")
    return out


def run(cell, *, seed: int, seconds: float, trace_dir: Optional[str] = None,
        t_process: Optional[float] = None) -> "harness.Run":
    tr = cell.traffic
    t_process = time.perf_counter() if t_process is None else t_process
    eng = build(cell, seed)
    jax.effects_barrier()
    if trace_dir is not None:
        trace.start(trace_dir)
    setup_s = time.perf_counter() - t_process
    compiles: List[float] = []
    try:
        loop, t_start, t_end = sc.serve(eng, tr, seed, seconds,
                                        compiles=compiles)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    routed = routed_by_step(loop, eng.routed)
    sc.drain(loop)
    peak = sc.memory_peak()
    eng.cache = eng.params = eng.tokens = None
    del eng
    gc.collect()

    sent = [s for s in loop.sent if s.t_submit <= t_end]
    failed = sum(1 for s in sent if not s.req.done)
    picked = sc.sample(loop, seed, tr["check_per_slot"])
    checks = []
    gaps = np.zeros(0)
    if picked:
        gaps, _ = reference_gaps(cell.config, seed, picked, tr["check_block"])
        checks += [harness.Check(name, value, cell.limits[name],
                                 value <= cell.limits[name])
                   for name, value in gap_readings(gaps).items()]
    least = tr["check_per_slot"] * tr["slots"] * tr["max_new"]
    checks.append(harness.Check("sampled_tokens", float(gaps.size),
                                float(least), gaps.size >= least))
    checks.append(harness.Check("failed_requests", float(failed), 0.0,
                                failed == 0))
    return harness.Run(
        cell=cell, seconds=seconds, setup_s=setup_s, attempted=len(sent),
        failed=failed, checks=checks, memory_peak_bytes=peak,
        records={"sent": sent, "t_start": t_start, "t_end": t_end,
                 "compiles": compiles,
                 "window_steps": loop.steps[:loop.window_steps],
                 "routed": routed[:loop.window_steps]})
