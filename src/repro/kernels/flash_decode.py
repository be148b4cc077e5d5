"""Flash-decode Pallas TPU kernel: one new token attending to a KV cache.

Each grid step processes one (batch, kv-head) pair and one KV-cache tile;
all G query heads of the KV head ride along in the sublane dimension (GQA
reuse — one K/V fetch serves G heads, the reuse the paper's Eq. 2 counts).
Emits per-shard (o, m, l) partials when ``return_partials`` so sequence-
sharded caches (SP, long_500k) can be merged with the distributed
log-sum-exp combine in models/attention.py::merge_partial_attn.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 512


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_out, l_out,
                   acc_ref, m_ref, l_ref, *, scale, block_k):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols >= len_ref[0], NEG_INF, s)        # (G, bk)

    m_prev = m_ref[...]                                  # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)   # UNNORMALIZED acc
        m_out[0, 0] = m_ref[...]
        l_out[0, 0] = l_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_k", "return_partials", "interpret"))
def flash_decode(q, k_cache, v_cache, cache_len, *,
                 block_k: int = DEFAULT_BLOCK_K,
                 return_partials: bool = False, interpret: bool = False):
    """q: (B, H, D); caches: (B, Hkv, S, D); cache_len: scalar int32.

    Returns (B, H, D), or ((B,H,D) unnormalized fp32 acc, m (B,H), l (B,H))
    when return_partials (for cross-shard merge).
    """
    B, H, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    G = H // Hkv
    scale = 1.0 / math.sqrt(D)
    bk = min(block_k, S)
    Sp = -(-S // bk) * bk
    if Sp != S:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    qg = q.reshape(B, Hkv, G, D)
    clen = jnp.minimum(jnp.asarray(cache_len, jnp.int32), S).reshape(1)

    grid = (B, Hkv, Sp // bk)
    acc, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(clen, qg, k_cache, v_cache)

    if return_partials:
        return (acc.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H))
    o = acc / jnp.maximum(l, 1e-30)
    return o.reshape(B, H, D).astype(q.dtype)
