"""Latent attention with routed and shared experts (moonlight-16b-a3b) at a
tiny width on the CPU, with seeded weights and float32 compute: the
absorbed decode against the expanded attention, one chip's share of the
experts against the uncut layer, no token dropped at any load, prefill and
cached decode (through the serving engine too) against the plain
reference, the parameter count against the benchmark's counts, the
sharding rules, and the engine's splice refusing a cache it cannot
place. Beside the dropless layer, the capacity-bounded one (reduced
dbrx-132b) against a token-by-token reference."""
from __future__ import annotations

import copy
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import counts_mla_moe  # noqa: E402
from chipbench.drivers.serve_closed_mla_moe import program_config  # noqa: E402
from chipbench.reference import mla_moe_lm  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.launch.serve import init_params  # noqa: E402
from repro.models import api, attention, layers, moe  # noqa: E402
from repro.parallel import sharding  # noqa: E402
from repro.serve import engine  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402

CONF = json.loads((ROOT / "chipbench" / "configs" /
                   "moonlight-16b-a3b.json").read_text())
SEED = 2**33 + 5
KEY = jax.random.PRNGKey(3)


def tiny_conf(dtype="float32"):
    """The cell's configuration at a width the CPU serves in seconds: 16
    published experts of which experts 4-7 are held, top-3."""
    conf = copy.deepcopy(CONF)
    conf.update(hidden_size=64, intermediate_size=128, num_hidden_layers=3,
                num_attention_heads=4, vocab_size=256, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                moe_intermediate_size=32, n_routed_experts=4,
                num_experts_per_tok=3, dtype=dtype)
    conf["deployment"] = dict(conf["deployment"],
                              published_n_routed_experts=16,
                              held_experts=[4, 8])
    return conf


@pytest.fixture
def f32_mlp(monkeypatch):
    """The dense MLP computes in bfloat16 whatever the compute dtype
    (``layers.apply_mlp``'s default); here it is given float32 like the
    rest."""
    mlp = layers.apply_mlp
    monkeypatch.setattr(layers, "apply_mlp", lambda kind, p, x, dtype=None:
                        mlp(kind, p, x, dtype=jnp.float32))


def test_absorbed_decode_equals_the_expanded_attention():
    cfg = program_config(tiny_conf())
    p = attention.mla_init(KEY, cfg)
    T, S = 9, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, cfg.d_model))
    q_nope, q_pe, lat = attention._mla_project(p, x, cfg,
                                               jnp.arange(T)[None])
    want = attention.mla_attend_expanded(p, q_nope[:, -1:], q_pe[:, -1:],
                                         lat, cfg)
    cache = tuple(jnp.concatenate(             # past the fill: masked
        [a, jax.random.normal(jax.random.PRNGKey(2), (2, S - T, a.shape[-1]))],
        axis=1) for a in lat)
    got = attention.mla_attend_absorbed(p, q_nope[:, -1:], q_pe[:, -1:],
                                        cache, T, cfg)
    assert got.shape == (2, 1, cfg.num_heads * cfg.v_head_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


def _uncut():
    cfg = dataclasses.replace(program_config(tiny_conf()), experts_held=0,
                              first_expert=0)
    return cfg, moe.moe_init(KEY, cfg)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Cut into 4 shares of 4 experts: each share holds the uncut layer's
    experts of its numbers, and the shares' routed parts, with the shared
    MLP counted once, add up to the uncut layer; so do their counts."""
    cfg, full = _uncut()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, cfg.d_model))
    want, want_counts = moe.moe_apply_held(full, x, cfg)
    routed, counts = 0.0, []
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=4, first_expert=first)
        p = moe.moe_init(KEY, share)
        for k in ("wg", "wu", "wd"):
            np.testing.assert_array_equal(p[k], full[k][first:first + 4])
        y, c = moe.moe_apply_held({k: v for k, v in p.items()
                                   if k != "shared"}, x, share)
        routed, counts = routed + y, counts + [c]
    shared = layers.apply_mlp(cfg.mlp, full["shared"], x, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want),
                               atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.concatenate(counts), want_counts)
    assert int(want_counts.sum()) == 2 * 7 * cfg.experts_per_token


def test_no_token_is_dropped_at_any_load():
    """Every token routed to the same three experts (the router's bias
    picks them): each token's result is what it gets alone, and the
    counts say every token went there."""
    cfg, p = _uncut()
    p = dict(p, router_bias=p["router_bias"].at[jnp.array([2, 5, 11])]
             .set(100.0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    y, counts = moe.moe_apply_held(p, x, cfg)
    alone = jnp.concatenate([moe.moe_apply_held(p, x[:, t:t + 1], cfg)[0]
                             for t in range(64)], axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(alone), atol=1e-5,
                               rtol=0)
    assert counts[2] == counts[5] == counts[11] == 64
    assert int(counts.sum()) == 64 * 3


def _capacity_reference(p, x, cfg, cap):
    """Token by token, in float64: the softmax router's top k, their
    weights renormalised, each chosen expert's SwiGLU weighted by its
    share; a choice of an expert that has already served ``cap`` (token,
    choice) pairs of the token's group of ``moe.GROUP_SIZE`` gives
    nothing."""
    x2 = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    logits = x2 @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    wg, wu, wd = (np.asarray(p[k], np.float64) for k in ("wg", "wu", "wd"))
    y = np.zeros_like(x2)
    for t, xt in enumerate(x2):
        if t % moe.GROUP_SIZE == 0:
            served = np.zeros(cfg.num_experts, int)
        top = np.argsort(-probs[t])[:cfg.experts_per_token]
        for e, w in zip(top, probs[t, top] / probs[t, top].sum()):
            if served[e] < cap:
                g, u = xt @ wg[e], xt @ wu[e]
                y[t] += w * ((g / (1 + np.exp(-g))) * u) @ wd[e]
            served[e] += 1
    return y.reshape(x.shape)


@pytest.mark.parametrize("cap", [moe.GROUP_SIZE, 1], ids=["ample", "one"])
def test_capacity_layer_matches_a_per_token_reference(cap):
    """Two groups of tokens through the capacity-bounded layer: with room
    for every token an expert could get, it is the plain top-k mixture;
    with room for one a group, each expert serves only the first token of
    each group that picked it, and later choices of it give nothing."""
    base = registry.get("dbrx-132b").reduced()
    E, k = base.num_experts, base.experts_per_token
    # moe_apply_einsum's capacity: max(1, int(group * k / E * factor))
    factor = cap * E / (moe.GROUP_SIZE * k)
    cfg = dataclasses.replace(base, compute_dtype="float32",
                              capacity_factor=factor)
    p = moe.moe_init(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, moe.GROUP_SIZE, cfg.d_model))
    y, _ = moe.moe_apply(p, x, cfg)
    want = _capacity_reference(p, x, cfg, cap)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5, rtol=1e-5)
    served = np.any(want != 0, axis=-1).sum()
    if cap == 1:            # at most E tokens of each group get anything
        assert 0 < served <= 2 * E
    else:
        assert served == 2 * moe.GROUP_SIZE


def _program(conf):
    cfg = program_config(conf)
    return cfg, init_params(cfg, SEED)


def test_prefill_then_cached_decode_matches_the_reference(f32_mlp):
    conf = tiny_conf()
    cfg, params = _program(conf)
    P, n = 10, 6
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, P + n))
    hidden, cache = api.prefill(cfg, params, {"tokens": jnp.asarray(
        toks[:, :P], jnp.int32)}, max_seq=P + n)
    got = [api.unembed(cfg, params, hidden[:, -1])]
    for t in range(n):
        logits, cache, _ = api.decode(cfg, params, cache,
                                      jnp.asarray(toks[:, P + t:P + t + 1]))
        got.append(logits[:, -1])
    got = np.stack([np.asarray(g) for g in got], axis=1)
    weights = mla_moe_lm.init_weights(conf, SEED, dtype=jnp.float32)
    pos = np.broadcast_to(np.arange(P - 1, P + n), (2, n + 1))
    want = np.asarray(mla_moe_lm.logits(conf, weights, toks, pos))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_the_engine_serves_the_references_tokens_and_counts_routing(f32_mlp):
    """Two waves through two slots of the engine (eager prefill, splice,
    the donated step): every served token is the reference's best to
    float32 rounding, and each decode step reports its routed pairs."""
    conf = tiny_conf()
    cfg, params = _program(conf)
    eng = ServeEngine(cfg, params, slots=2, max_seq=16)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 6),
                    max_new=6) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 4 and len(eng.routed) == eng.steps
    for c in eng.routed:
        assert c.shape == (cfg.num_layers - 1, 4) and c.dtype == np.int32
        assert c.sum(axis=1).max() <= 2 * cfg.experts_per_token
    assert sum(int(c.sum()) for c in eng.routed) > 0
    weights = mla_moe_lm.init_weights(conf, SEED, dtype=jnp.float32)
    toks = np.stack([np.concatenate([r.prompt, r.out[:-1]]) for r in reqs])
    pos = np.broadcast_to(np.arange(5, 11), (4, 6))
    ref = np.asarray(mla_moe_lm.logits(conf, weights, toks, pos))
    served = np.take_along_axis(ref, np.stack([r.out for r in reqs])[..., None],
                                axis=-1)[..., 0]
    assert (ref.max(-1) - served).max() < 1e-4


def test_param_count_matches_the_benchmarks_counts():
    cfg = program_config(CONF)
    assert cfg.param_count() == counts_mla_moe.matmul_params(CONF) \
        == 3_364_487_168
    shapes = jax.eval_shape(lambda k: api.init(cfg, k), jax.random.PRNGKey(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    assert made == counts_mla_moe.weight_count(CONF)
    assert counts_mla_moe.latent_bytes_per_token(CONF) == 27 * 576 * 2
    # a token activates 6 of the deployment's 64 experts and both shared
    per_layer = cfg.active_param_count() - cfg.param_count()
    assert per_layer == -26 * 2 * 3 * 2048 * 1408


def test_sharding_rules_place_the_new_parameters():
    class Mesh:
        shape = {"data": 2, "model": 2}
        axis_names = ("data", "model")
    cfg = program_config(tiny_conf())
    shapes = jax.eval_shape(lambda: api.init(cfg, KEY))
    specs = sharding.param_specs(cfg, shapes, Mesh())
    blocks = specs["blocks"]
    assert tuple(blocks["attn"]["wq"]["w"]) == (None, "data", "model")
    assert tuple(blocks["attn"]["wkv_a"]["w"]) == (None, "data", None)
    assert tuple(blocks["attn"]["wkv_b"]["w"]) == (None, None, "model")
    assert tuple(blocks["moe"]["wg"]) == (None, "model", "data", None)
    assert tuple(blocks["moe"]["shared"]["wd"]["w"]) == (None, "model", "data")
    assert tuple(specs["dense_blocks"]["mlp"]["wg"]["w"]) == \
        (None, "data", "model")
    cache = sharding.cache_specs(cfg, Mesh(), batch=2)
    made = jax.eval_shape(lambda: api.init_cache(cfg, 2, 8))
    assert set(cache) == set(made) == {"c_kv", "k_pe", "dense_c_kv",
                                       "dense_k_pe", "idx"}
    assert made["c_kv"].shape == (2, 2, 8, 32)
    assert made["dense_k_pe"].shape == (1, 2, 8, 8)


def test_splice_refuses_a_cache_array_without_a_batch_axis():
    splice = engine._make_splice(4, {"a": (0, 1, 2), "idx": ()})
    cache = {"a": jnp.zeros((3, 4, 5)), "idx": jnp.int32(0)}
    got = splice(cache, {"a": jnp.ones((3, 1, 5)), "idx": jnp.int32(7)}, 2)
    assert float(got["a"][:, 2].min()) == 1.0 and float(got["a"].sum()) == 15
    assert int(got["idx"]) == 7
    with pytest.raises(ValueError, match="no batch axis"):
        splice(cache, {"a": jnp.ones((3, 2, 5)), "idx": jnp.int32(7)}, 2)
