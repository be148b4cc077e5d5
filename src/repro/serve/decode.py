"""Serve steps: prefill and single-token decode, jit-ready.

``make_prefill_step`` returns the prefill the serving engine compiles once
per engine: the prompt's cache and its first greedy token.
``make_serve_step`` returns the decode step: one new token per sequence
against a resident KV cache (or SSM state), greedy-sampled. The dry-run
(``launch/specs.py``) lowers the same two functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from repro.models import api


def make_prefill_step(cfg, *, max_seq: int):
    def prefill_step(params, batch):
        hidden, cache = api.prefill(cfg, params, batch, max_seq=max_seq)
        logits = api.unembed(cfg, params, hidden[:, -1:])
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache
    return prefill_step


def make_serve_step(cfg):
    """The decode step; where ``cfg.reports_routing`` it also returns the
    step's routed counts (MoE layers, held experts)."""
    def serve_step(params, cache, tokens):
        """tokens: (B, 1) -> (next_token (B,1), new_cache[, routed])."""
        logits, cache, *routed = api.decode(cfg, params, cache, tokens)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return (next_tok, cache, *routed)
    return serve_step


def _is_axes(x):
    return isinstance(x, tuple)


def store(cache, axes):
    """Each array of ``cache`` with its axes in the order ``axes`` gives."""
    return jax.tree.map(lambda a, x: jnp.transpose(x, a), axes, cache,
                        is_leaf=_is_axes)


def load(stored, axes):
    """The inverse of :func:`store`."""
    return jax.tree.map(lambda a, x: jnp.transpose(x, np.argsort(a)), axes,
                        stored, is_leaf=_is_axes)


def cache_axes(cfg, params, *, batch: int, max_seq: int, sharding):
    """For each array of ``make_serve_step``'s cache (``batch`` x
    ``max_seq`` on ``sharding``), the order of its axes, major to minor,
    in the layout the compiler picks for it when left to choose
    (``Layout.AUTO``): the one its attention reads. ``params`` may be
    arrays or shapes."""
    cache = jax.eval_shape(lambda: api.init_cache(
        cfg, batch, max_seq, dtype=jnp.dtype(cfg.compute_dtype)))
    auto = Format(Layout.AUTO, sharding)
    formats = jax.tree.map(lambda c: auto if c.ndim else sharding, cache)
    outs = (sharding, formats) + (sharding,) * cfg.reports_routing
    chosen = jax.jit(make_serve_step(cfg), in_shardings=(None, formats, None),
                     out_shardings=outs).lower(
        params, cache, jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    ).compile().input_formats[0][1]
    return jax.tree.map(lambda f: tuple(f.layout.major_to_minor), chosen)


def compile_serve_step(cfg, params, axes, *, batch: int, max_seq: int,
                       sharding):
    """``make_serve_step`` compiled for a cache of ``batch`` x ``max_seq``
    on ``sharding``, donated and stored in the order ``axes`` gives
    (:func:`store`), each array in the default layout. With the order
    :func:`cache_axes` finds, its transposes in and out cost nothing and
    the step updates the cache in place. (A cache held in a layout of its
    own instead would not survive JAX's persistent cache: an executable
    read back from it, on jax 0.9.0, returns its results in the default
    layout.) ``params`` may be arrays or shapes."""
    step = make_serve_step(cfg)

    def serve_step(params, stored, tokens):
        tok, cache, *routed = step(params, load(stored, axes), tokens)
        return (tok, store(cache, axes), *routed)
    stored = jax.tree.map(
        lambda c: jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=sharding),
        jax.eval_shape(lambda: store(api.init_cache(
            cfg, batch, max_seq, dtype=jnp.dtype(cfg.compute_dtype)), axes)))
    return jax.jit(serve_step, donate_argnums=(1,)).lower(
        params, stored,
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=sharding)
    ).compile()
