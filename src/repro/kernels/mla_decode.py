"""Absorbed latent (MLA) decode attention, Pallas TPU kernel.

One new token a sequence attends over one layer of the stacked latent
cache, where it is stored: c_kv (L, B, S, r) and k_pe (L, B, S, rope).
The layer and the fill (``cache_len``, shared by every sequence) are
prefetched scalars, so the index maps pick the layer's tiles straight out
of the stack and clamp the sequence block to the last one below the fill:
no layer is sliced out, and no tile at or past the fill is fetched. Each
(c_kv, k_pe) tile is read once and serves both matmuls: the scores of all
heads against it, an online-softmax update in float32, and the weighted
sum of the same c_kv tile. Operands stay in the compute dtype, with
float32 accumulation; the probabilities are cast to it for the weighted
sum, as in ``models.attention.mla_latent_attend``, the plain jnp form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# bytes of one c_kv tile (block_b slots x block_s positions x r)
_TILE_BYTES = 2 << 20


def blocks(batch: int, seq: int, rank: int, itemsize: int):
    """(block_b, block_s) for a cache of ``batch`` x ``seq`` x ``rank``:
    the longest block of positions up to 512 that divides ``seq``, and as
    many slots a grid step as keep a c_kv tile within 2 MiB; None where no
    block of 128 or more positions divides ``seq``."""
    block_s = next((b for b in (512, 256, 128) if seq % b == 0), None)
    if block_s is None:
        return None
    fit = max(1, _TILE_BYTES // (block_s * rank * itemsize))
    block_b = max(d for d in range(1, batch + 1)
                  if batch % d == 0 and d <= fit)
    return block_b, block_s


def tileable(q_c, q_pe, c_kv, k_pe) -> bool:
    """Whether the kernel tiles these shapes: lane-wide latent and heads
    in whole sublane groups, one dtype, and a block that divides S."""
    _, B, S, r = c_kv.shape
    H = q_c.shape[1]
    return (r % 128 == 0 and H % 8 == 0
            and len({q_c.dtype, q_pe.dtype, c_kv.dtype, k_pe.dtype}) == 1
            and blocks(B, S, r, c_kv.dtype.itemsize) is not None)


def _kernel(layer_ref, len_ref, qc_ref, qpe_ref, c_ref, pe_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, block_b, block_s):
    del layer_ref
    j = pl.program_id(1)
    n = len_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_s < n)
    def _step():
        for i in range(block_b):
            c = c_ref[i]                                       # (bs, r)
            s = jax.lax.dot_general(
                qc_ref[i], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(
                qpe_ref[i], pe_ref[i], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # (H, bs)
            s = s * scale
            cols = j * block_s + jax.lax.broadcasted_iota(jnp.int32,
                                                          s.shape, 1)
            s = jnp.where(cols < n, s, NEG_INF)
            m_prev = m_ref[i]                                  # (H, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[i] = l_ref[i] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[i] = m_new
            acc_ref[i] = acc_ref[i] * corr + jax.lax.dot_general(
                p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # (H, r)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = acc_ref[...] / l_ref[...]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode(q_c, q_pe, c_kv, k_pe, layer, cache_len, *, scale: float,
               interpret: bool = False):
    """q_c (B, H, r), q_pe (B, H, rope): the absorbed query; c_kv (L, B, S,
    r), k_pe (L, B, S, rope): every layer's cache, in the query's dtype,
    k_pe read positions-minor (the layout the TPU gives an array whose last
    axis is narrower than a lane, which the caller can store for free);
    layer, cache_len: int scalars, 1 <= cache_len <= S. -> (B, H, r)
    float32, the softmax-weighted sum of layer ``layer``'s c_kv over its
    first ``cache_len`` positions, scores scaled by ``scale``."""
    B, H, r = q_c.shape
    _, _, S, rope = k_pe.shape
    bb, bs = blocks(B, S, r, c_kv.dtype.itemsize)

    def last(j, n):
        # past the fill the block index stays on the last one below it,
        # so the pipeline fetches nothing new
        return jnp.minimum(j, jnp.maximum(n[0] - 1, 0) // bs)

    def c_tile(b, j, lay, n):
        return lay[0], b, last(j, n), 0

    def pe_tile(b, j, lay, n):
        return lay[0], b, 0, last(j, n)

    def per_slot(b, j, lay, n):
        return b, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // bb, S // bs),
        in_specs=[
            pl.BlockSpec((bb, H, r), per_slot),
            pl.BlockSpec((bb, H, rope), per_slot),
            pl.BlockSpec((None, bb, bs, r), c_tile),
            pl.BlockSpec((None, bb, rope, bs), pe_tile),
        ],
        out_specs=pl.BlockSpec((bb, H, r), per_slot),
        scratch_shapes=[
            pltpu.VMEM((bb, H, 1), jnp.float32),
            pltpu.VMEM((bb, H, 1), jnp.float32),
            pltpu.VMEM((bb, H, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_b=bb, block_s=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode_attend",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(cache_len, (1,)).astype(jnp.int32), q_c, q_pe, c_kv,
      jnp.swapaxes(k_pe, 2, 3))
