"""The serving engine's K/V cache: its decode step writes one position per
layer into the cache it was given, and its tokens are a cache-free
forward's greedy tokens, wave after wave of requests through the same
slots; with the cache's axes stored in the order the compiler chose here
(the logical one on the CPU) and in the order a v5e chooses. Its prefill
is one program, built at the first admission, that later admissions only
dispatch, and gives the eager prefill's first token and cache (dense and
latent caches). The hybrid and encoder-decoder decode steps write their
stacked K/V caches one position per layer as well."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

from repro.configs import registry
from repro.models import api, transformer
from repro.serve.decode import load
from repro.serve import engine
from repro.serve.engine import Request, ServeEngine

KEY = jax.random.PRNGKey(0)
PROMPT, MAX_SEQ = 6, 16


# L, S, H, B, D: the order of the cache's axes in the layout the TPU
# compiler picks for both benchmark cells' decode steps on a v5e
V5E_AXES = (0, 2, 3, 1, 4)


@pytest.fixture(params=["compiler", "v5e"])
def axes(request, monkeypatch):
    if request.param == "v5e":
        def v5e(cfg, params, *, batch, max_seq, sharding):
            return jax.tree.map(
                lambda c: V5E_AXES if c.ndim else (),
                jax.eval_shape(lambda: api.init_cache(cfg, batch, max_seq)))
        monkeypatch.setattr(engine, "cache_axes", v5e)
    return request.param


def _model(arch):
    """A float32 reduced config of ``arch`` with three layers, so that a
    layer written one index off lands on another layer's K/V."""
    cfg = dataclasses.replace(registry.get(arch).reduced(), num_layers=3,
                              compute_dtype="float32")
    return cfg, api.init(cfg, KEY)


def _requests(cfg, n, max_new):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, PROMPT),
                    max_new=max_new) for i in range(n)]


def _logical(eng, cache):
    """The engine's cache on the host, axes in their logical order."""
    return jax.tree.map(np.asarray, load(cache, eng.cache_axes))


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen2.5-3b"],
                         ids=["mha", "gqa"])
def test_decode_step_writes_one_position_per_layer(arch, axes):
    cfg, params = _model(arch)
    eng = ServeEngine(cfg, params, slots=2, max_seq=MAX_SEQ)
    for r in _requests(cfg, 2, 8):
        eng.submit(r)
    eng.step()                               # admits both, decodes once
    if axes == "v5e":
        assert eng.cache["k"].shape[1] == MAX_SEQ
    # copies: a host view of the cache's own buffer would keep the step
    # from taking it
    before = _logical(eng, jax.tree.map(jnp.copy, eng.cache))
    tokens, k_in = np.asarray(eng.tokens), eng.cache["k"]
    eng.step()
    assert k_in.is_deleted()                 # the step took the cache's buffer
    after = _logical(eng, eng.cache)

    idx = int(before["idx"])
    assert int(after["idx"]) == idx + 1
    others = np.arange(MAX_SEQ) != idx
    for name in ("k", "v"):
        np.testing.assert_array_equal(after[name][:, :, others],
                                      before[name][:, :, others])

    # layer by layer, un-jitted, outside the scan: each layer written into
    # the cache as it stood before the step
    x = transformer._embed_inputs(cfg, params, jnp.asarray(tokens), None)
    positions = jnp.full((1, 1), idx, jnp.int32)
    for layer in range(cfg.num_layers):
        blk = jax.tree.map(lambda p: p[layer], params["blocks"])
        x, (k, v), _ = transformer._block_apply(
            blk, x, cfg, positions=positions, cache_index=idx,
            kv=(before["k"], before["v"]), cache_layer=layer)
        np.testing.assert_allclose(after["k"][layer, :, idx],
                                   k[layer, :, idx], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(after["v"][layer, :, idx],
                                   v[layer, :, idx], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["zamba2-7b", "whisper-large-v3"],
                         ids=["hybrid", "encdec"])
def test_hybrid_and_encdec_decode_write_one_position_per_layer(arch):
    """A hybrid's shared-attention groups and an encoder-decoder's decoder
    layers write their stacked K/V cache in place too: two donated decode
    steps after a prefill, each giving a cache-free forward's logits and
    changing no cache position but the one it writes."""
    base = registry.get(arch).reduced()
    # three attention layers, so that a layer written one index off lands
    # on another layer's K/V
    more = (dict(hybrid_groups=3) if base.family == "hybrid"
            else dict(dec_layers=3))
    cfg = dataclasses.replace(base, compute_dtype="float32", **more)
    params = api.init(cfg, KEY)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, PROMPT + 2)),
                       jnp.int32)
    batch = {"tokens": toks[:, :PROMPT]}
    if cfg.family == "encdec":
        batch["frames"] = 0.1 * jax.random.normal(
            KEY, (2, cfg.frontend_len, cfg.d_model))
    _, cache = api.prefill(cfg, params, batch, max_seq=MAX_SEQ)
    step = jax.jit(functools.partial(api.decode, cfg), donate_argnums=(1,))
    names = ("attn_k", "attn_v") if cfg.family == "hybrid" else ("k", "v")
    for idx in (PROMPT, PROMPT + 1):
        before = {n: np.array(cache[n]) for n in names}
        logits, cache = step(params, cache, toks[:, idx:idx + 1])
        assert int(cache["idx"]) == idx + 1
        others = np.arange(MAX_SEQ) != idx
        for n in names:
            after = np.asarray(cache[n])
            np.testing.assert_array_equal(after[:, :, others],
                                          before[n][:, :, others])
            assert np.all(after[:, :, idx] != 0)
        hidden, _ = api.forward_hidden(
            cfg, params, dict(batch, tokens=toks[:, :idx + 1]), remat="none")
        want = api.unembed(cfg, params, hidden[:, -1:])
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_two_waves_match_a_cache_free_forward(axes):
    """Four requests on two slots: the second wave is spliced over the
    first's cache, whose later positions it must not see."""
    cfg, params = _model("qwen2.5-3b")
    eng = ServeEngine(cfg, params, slots=2, max_seq=MAX_SEQ)
    reqs = _requests(cfg, 4, 5)
    for r in reqs:
        eng.submit(r)
    assert sorted(r.rid for r in eng.run()) == [0, 1, 2, 3]

    seqs = np.stack([np.concatenate([r.prompt, r.out]) for r in reqs])
    hidden, _ = api.forward_hidden(cfg, params, {"tokens": jnp.asarray(seqs)},
                                   remat="none")
    # position PROMPT-1+t predicts output token t
    logits = api.unembed(cfg, params, hidden[:, PROMPT - 1:-1])
    np.testing.assert_array_equal(np.argmax(np.asarray(logits), -1),
                                  seqs[:, PROMPT:])



COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PREFILL_ARCHS = pytest.mark.parametrize(
    "arch", ["qwen2.5-3b", "moonlight-16b-a3b"], ids=["gqa", "mla-moe"])


@PREFILL_ARCHS
def test_later_admissions_build_no_program(arch):
    """Once the first request is served, five more of its length, in three
    waves through both slots, compile nothing: not the prefill, the
    splice, the token write nor the decode step."""
    cfg, params = _model(arch)
    eng = ServeEngine(cfg, params, slots=2, max_seq=MAX_SEQ)
    first, *later = _requests(cfg, 6, 3)
    eng.submit(first)
    eng.run()
    assert eng.prefill_compiles == 1
    compiles = []

    def count(event, secs, **_):
        if event == COMPILE_EVENT:
            compiles.append(event)
    monitoring.register_event_duration_secs_listener(count)
    try:
        for r in later:
            eng.submit(r)
        assert len(eng.run()) == 5
    finally:
        monitoring.unregister_event_duration_listener(count)
    assert compiles == [] and eng.prefill_compiles == 1


@PREFILL_ARCHS
def test_compiled_prefill_equals_the_eager_one(arch):
    """Two requests admitted into both slots: each first token is the
    eager prefill's and unembed's greedy token, and each slot holds the
    eager prefill's cache at the prompt's positions."""
    cfg, params = _model(arch)
    eng = ServeEngine(cfg, params, slots=2, max_seq=MAX_SEQ)
    reqs = _requests(cfg, 2, 4)
    for r in reqs:
        eng.submit(r)
    eng.step()                  # admits both, then writes position PROMPT
    cache = _logical(eng, eng.cache)
    for slot, r in enumerate(reqs):
        toks = jnp.asarray(r.prompt, jnp.int32)[None]
        hidden, want = api.prefill(cfg, params, {"tokens": toks},
                                   max_seq=MAX_SEQ)
        logits = api.unembed(cfg, params, hidden[:, -1])
        assert r.out[0] == int(jnp.argmax(logits[0]))
        for name, got in cache.items():
            if name != "idx":
                np.testing.assert_allclose(
                    got[:, slot, :PROMPT],
                    np.asarray(want[name])[:, 0, :PROMPT],
                    rtol=1e-5, atol=1e-5, err_msg=name)
