"""The float32 reference against the program at tiny width: the same
weights from the same seed, and prefill followed by cached decode giving
the reference's full-forward logits. The float8 control reads far wider
gaps than the program."""
from __future__ import annotations

import copy

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import tiny_cell

from chipbench.drivers import serve_closed as sc
from chipbench.reference import dense_lm

CELLS = ["qwen2.5-3b.long-prompt", "olmo-1b.long-decode"]
SEED = 2**33 + 17          # seeds pass 32 bits


@pytest.mark.parametrize("name", CELLS)
def test_weights_are_the_programs(name):
    from repro.launch.serve import init_params
    conf = tiny_cell(name).config
    prog = init_params(sc.program_config(conf), SEED)
    ref = dense_lm.init_weights(conf, SEED)
    blocks = prog["blocks"]
    pairs = [(ref["emb"], prog["emb"]["table"])] + [
        (ref["layers"][k], blocks["attn"][k]["w"])
        for k in ("wq", "wk", "wv", "wo")] + [
        (ref["layers"][k], blocks["mlp"][k]["w"]) for k in ("wg", "wu", "wd")]
    if conf["qkv_bias"]:
        pairs += [(ref["layers"]["b" + k[1]], blocks["attn"][k]["b"])
                  for k in ("wq", "wk", "wv")]
    if conf["norm"] == "rmsnorm":
        pairs += [(ref["layers"]["attn_norm"], blocks["attn_norm"]["scale"]),
                  (ref["final_norm"], prog["final_norm"]["scale"])]
    for a, b in pairs:
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("name", CELLS)
def test_prefill_then_cached_decode_matches_the_full_forward(name,
                                                          monkeypatch):
    """With float32 compute the program's logits through its prefill and
    its cache agree with the reference's full forward to float32 rounding.
    The program's MLP runs in bfloat16 whatever the compute dtype
    (``layers.apply_mlp``'s default; left as it is, the gap is 0.02), so
    here it is given the float32 the rest of the model computes in."""
    from repro.launch.serve import init_params
    from repro.models import api, layers
    mlp = layers.apply_mlp
    monkeypatch.setattr(layers, "apply_mlp", lambda kind, p, x: mlp(
        kind, p, x, dtype=jnp.float32))
    conf = copy.deepcopy(tiny_cell(name).config)
    conf["dtype"] = "float32"
    cfg = sc.program_config(conf)
    params = init_params(cfg, SEED)
    P, n = 12, 6
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, P + n))
    hidden, cache = api.prefill(cfg, params, {"tokens": jnp.asarray(
        toks[:, :P], jnp.int32)}, max_seq=P + n)
    got = [api.unembed(cfg, params, hidden[:, -1])]
    for t in range(n):
        logits, cache = api.decode(cfg, params, cache,
                                   jnp.asarray(toks[:, P + t:P + t + 1]))
        got.append(logits[:, -1])
    got = np.stack([np.asarray(g) for g in got], axis=1)
    weights = dense_lm.init_weights(conf, SEED, dtype=jnp.float32)
    pos = np.broadcast_to(np.arange(P - 1, P + n), (2, n + 1))
    want = np.asarray(dense_lm.logits(conf, weights, toks, pos))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


@pytest.mark.parametrize("name", CELLS)
def test_the_float8_control_reads_wider_gaps_than_the_program(name):
    """The control: the reference with float8 matmuls in the program's
    place: it fails the limit that the served bf16 tokens keep."""
    cell = tiny_cell(name, check_per_slot=1)
    eng = sc.build(cell, 5)
    loop, _, _ = sc.serve(eng, cell.traffic, 5, 0.0)
    sc.drain(loop)
    picked = sc.sample(loop, 5, 1)
    gaps, ctl = sc.reference_gaps(cell.config, 5, picked, 2,
                                  control=dense_lm.fp8_linear)
    assert gaps.size == ctl.size == 4 * 8
    assert gaps.max() <= cell.limits["max_logit_gap"] < ctl.max()
