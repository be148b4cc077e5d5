"""Attention: GQA projections + FlashAttention-style chunked online softmax.

``flash_ref`` is the pure-jnp online-softmax implementation (algorithmically
FlashAttention, scanned over KV chunks): the attention of every family's
training forward and prefill unless a caller passes ``attn_fn``, and the
oracle for the Pallas kernels in ``repro.kernels``. Decode attends over the
cache with ``decode_attend`` (or ``mla_attend_absorbed``, whose core runs
the ``kernels.mla_decode`` kernel where the program is lowered for a TPU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import mla_decode
from repro.models import layers
from repro.parallel import ctx as pctx

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Reference implementations
# ---------------------------------------------------------------------------

def attention_naive(q, k, v, *, causal: bool, q_offset=0):
    """Materializing reference. q:(B,L,H,D) k/v:(B,S,Hkv,D) -> (B,L,H,D)."""
    B, L, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, L, Hkv, G, D)
    s = jnp.einsum("blhgd,bshd->bhgls", qg.astype(jnp.float32), k.astype(jnp.float32))
    s *= 1.0 / math.sqrt(D)
    if causal:
        row = jnp.arange(L)[:, None] + q_offset
        col = jnp.arange(S)[None, :]
        s = jnp.where(col <= row, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgls,bshd->blhgd", p, v.astype(jnp.float32))
    return o.reshape(B, L, H, D).astype(q.dtype)


def flash_ref(q, k, v, *, causal: bool, q_offset=0, chunk: int = 512):
    """Online-softmax attention scanned over KV chunks (pure jnp).

    Never materializes the (L, S) score matrix for more than one KV chunk;
    this is the FlashAttention dataflow expressed at the XLA level. ``v``
    may have another head size than ``q`` and ``k`` (latent attention).
    """
    B, L, H, D = q.shape
    S, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    chunk = min(chunk, S)
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n, chunk, Hkv, Dv).transpose(1, 0, 2, 3, 4)

    qg = q.reshape(B, L, Hkv, G, D).astype(jnp.float32) * (1.0 / math.sqrt(D))
    row = jnp.arange(L)[:, None] + q_offset

    def body(carry, kv):
        m, l, acc, j = carry
        kj, vj = kv
        s = jnp.einsum("blhgd,bchd->blhgc", qg, kj.astype(jnp.float32))
        col = j * chunk + jnp.arange(chunk)[None, :]          # (1, chunk)
        if causal:
            mask = (col > row) | (col >= S)                   # (L, chunk)
        else:
            mask = jnp.broadcast_to(col >= S, (L, chunk))
        s = jnp.where(mask[None, :, None, None, :], NEG_INF, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "blhgc,bchd->blhgd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new, j + 1), None

    m0 = jnp.full((B, L, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, L, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((B, L, Hkv, G, Dv), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, acc0, 0), (kc, vc))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(B, L, H, Dv).astype(q.dtype)


def decode_attend(q, k_cache, v_cache, cache_len, *, q_offset=None):
    """Single-token decode over a (possibly longer-than-filled) KV cache.

    q: (B, 1, H, D); caches: (B, S_max, Hkv, D); cache_len: scalar or (B,)
    Positions >= cache_len are masked.
    """
    B, L, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, L, Hkv, G, D).astype(jnp.float32) * (1.0 / math.sqrt(D))
    s = jnp.einsum("blhgd,bshd->blhgs", qg, k_cache.astype(jnp.float32))
    # seq-sharded caches: keep scores sharded on S (partial-softmax psum)
    # instead of letting XLA all-gather the cache per layer
    s = pctx.constrain(s, "scores_dec")
    pos = jnp.arange(S)
    valid = pos[None, :] < jnp.broadcast_to(jnp.asarray(cache_len), (B,))[:, None]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    o = jnp.einsum("blhgs,bshd->blhgd", p, v_cache.astype(jnp.float32))
    o = o / jnp.sum(p, axis=-1)[..., None]
    return o.reshape(B, L, H, D).astype(q.dtype)


def decode_attend_partial(q, k_shard, v_shard, valid_mask):
    """Shard-local flash decode for sequence-sharded KV caches (SP).

    Returns (o_partial(fp32), m(fp32), l(fp32)) for a distributed
    log-sum-exp merge across sequence shards (see merge_partial_attn).
    q: (B,1,H,D); k/v_shard: (B,S_loc,Hkv,D); valid_mask: (B,S_loc) bool.
    """
    B, L, H, D = q.shape
    Hkv = k_shard.shape[2]
    G = H // Hkv
    qg = q.reshape(B, L, Hkv, G, D).astype(jnp.float32) * (1.0 / math.sqrt(D))
    s = jnp.einsum("blhgd,bshd->blhgs", qg, k_shard.astype(jnp.float32))
    s = jnp.where(valid_mask[:, None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("blhgs,bshd->blhgd", p, v_shard.astype(jnp.float32))
    return o, m, l


def merge_partial_attn(o_parts, m_parts, l_parts, axis=0):
    """Merge per-shard (o, m, l) partials along a leading shard axis."""
    m = jnp.max(m_parts, axis=axis)
    corr = jnp.exp(m_parts - jnp.expand_dims(m, axis))
    l = jnp.sum(l_parts * corr, axis=axis)
    o = jnp.sum(o_parts * corr[..., None], axis=axis)
    return o / jnp.maximum(l, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# Attention block (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------

def attn_init(key, cfg):
    if cfg.kv_lora_rank:
        return mla_init(key, cfg)
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": layers.dense_init(ks[0], d, cfg.num_heads * hd, bias=cfg.qkv_bias or cfg.bias),
        "wk": layers.dense_init(ks[1], d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias or cfg.bias),
        "wv": layers.dense_init(ks[2], d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias or cfg.bias),
        "wo": layers.dense_init(ks[3], cfg.num_heads * hd, d, bias=cfg.bias),
    }


def attn_apply(p, x, cfg, *, positions, kv_cache=None, cache_index=None,
               cache_layer=None, cross_kv=None, attn_fn=None, use_rope=True):
    """Returns (out, new_kv) where new_kv is (k, v) of this call's tokens.

    With ``cfg.kv_lora_rank`` the layer is latent attention
    (:func:`mla_apply`). kv_cache: optional (k_cache, v_cache), every
    layer's cache, (L, B, S_max, Hkv, D), given with ``cache_layer``, the
    layer this call is (decode path, x is (B,1,d)): this call's tokens are
    written into that layer at ``cache_index``, the layer is read where it
    is stored, and both caches are returned. cross_kv: precomputed (k, v)
    for cross-attention (no rope, no cache write).
    """
    if cfg.kv_lora_rank:
        return mla_apply(p, x, cfg, positions=positions, kv_cache=kv_cache,
                         cache_index=cache_index, cache_layer=cache_layer,
                         attn_fn=attn_fn)
    B, L, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    q = layers.dense(p["wq"], x, dtype=dt).reshape(B, L, H, hd)
    if cross_kv is not None:
        k, v = cross_kv
        if use_rope:
            q = layers.rope(q, positions, cfg.rope_theta)
        o = (attn_fn or flash_ref)(q, k, v, causal=False)
        return layers.dense(p["wo"], o.reshape(B, L, H * hd), dtype=dt), None

    k = layers.dense(p["wk"], x, dtype=dt).reshape(B, L, Hkv, hd)
    v = layers.dense(p["wv"], x, dtype=dt).reshape(B, L, Hkv, hd)
    if use_rope:
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, positions, cfg.rope_theta)

    if kv_cache is not None:
        k_cache, v_cache = kv_cache
        idx = cache_index
        at = (cache_layer, 0, idx, 0, 0)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k[None].astype(k_cache.dtype), at)
        v_cache = jax.lax.dynamic_update_slice(v_cache, v[None].astype(v_cache.dtype), at)
        k_att = jax.lax.dynamic_index_in_dim(k_cache, cache_layer, keepdims=False)
        v_att = jax.lax.dynamic_index_in_dim(v_cache, cache_layer, keepdims=False)
        o = decode_attend(q, k_att, v_att, idx + L)
        out = layers.dense(p["wo"], o.reshape(B, L, H * hd), dtype=dt)
        return out, (k_cache, v_cache)

    o = (attn_fn or flash_ref)(q, k, v, causal=cfg.causal)
    out = layers.dense(p["wo"], o.reshape(B, L, H * hd), dtype=dt)
    # keep collected KV sharded (prefill cache assembly): without this the
    # scan's stacked ys replicate over 'model' when Hkv < TP degree
    return out, (pctx.constrain(k, "kv_collect"), pctx.constrain(v, "kv_collect"))


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2/V3 without a query LoRA)
#
#   q = x W_q                      -> q_nope (H x nope), q_pe (H x rope)
#   [c_kv | k_pe] = x W_kv_a;  c_kv <- RMSNorm(c_kv)
#   [k_nope | v] = c_kv W_kv_b     (per head)
#   RoPE on q_pe and on k_pe, one k_pe shared by every head
#   o = softmax([q_nope | q_pe] . [k_nope | k_pe] / sqrt(nope + rope)) v
#   out = o W_o
#
# The cache holds what the keys and values are made from: the normalised
# c_kv and the roped k_pe, kv_lora_rank + rope values a token, in two
# arrays (a latent is the pair). Decode is absorbed: q_nope W_UK^T attends
# over c_kv, q_pe over k_pe, and W_UV is applied after the weighted sum, so
# no key or value is ever expanded.
# ---------------------------------------------------------------------------

# the latent's RMSNorm keeps its module's default eps in DeepSeek-V3's code
# (the decoder's norms take rms_norm_eps)
MLA_NORM_EPS = 1e-6


def mla_init(key, cfg):
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": layers.dense_init(ks[0], d, H * (nope + rope)),
        "wkv_a": layers.dense_init(ks[1], d, r + rope),
        "kv_norm": layers.norm_init("rmsnorm", r),
        "wkv_b": layers.dense_init(ks[2], r, H * (nope + vd)),
        "wo": layers.dense_init(ks[3], H * vd, d),
    }


def _mla_project(p, x, cfg, positions):
    """x (B, T, d) -> q_nope (B, T, H, nope), roped q_pe (B, T, H, rope)
    and the tokens' latent: normalised c_kv (B, T, r), roped k_pe (B, T,
    rope)."""
    B, T, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    q = layers.dense(p["wq"], x, dtype=dt).reshape(B, T, H, nope + rope)
    q_pe = layers.rope(q[..., nope:], positions, cfg.rope_theta,
                       interleaved=True)
    kv = layers.dense(p["wkv_a"], x, dtype=dt)
    c = layers.apply_norm("rmsnorm", p["kv_norm"], kv[..., :r],
                          eps=MLA_NORM_EPS)
    k_pe = layers.rope(kv[..., None, r:], positions, cfg.rope_theta,
                       interleaved=True)[..., 0, :]
    return q[..., :nope], q_pe, (c, k_pe)


def _mla_wkv_b(p, cfg):
    """W_kv_b as (r, H, nope + v): per head, W_UK then W_UV."""
    r, H = cfg.kv_lora_rank, cfg.num_heads
    w = p["wkv_b"]["w"].astype(jnp.dtype(cfg.compute_dtype))
    return w.reshape(r, H, cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_attend_expanded(p, q_nope, q_pe, latent, cfg, *, attn_fn=None):
    """Causal attention over ``latent`` (c_kv (B, S, r), k_pe (B, S,
    rope)) with its keys and values expanded through W_kv_b: the prefill's
    path, and the plain form the absorbed decode is checked against.
    -> (B, T, H * v)."""
    c, k_pe = latent
    B, S, _ = c.shape
    T = q_nope.shape[1]
    H, nope = cfg.num_heads, cfg.qk_nope_head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    kv = jnp.einsum("bsc,chn->bshn", c.astype(dt), _mla_wkv_b(p, cfg))
    k_pe = jnp.broadcast_to(k_pe[:, :, None].astype(dt),
                            (B, S, H, cfg.qk_rope_head_dim))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    o = (attn_fn or flash_ref)(q, k, kv[..., nope:], causal=cfg.causal,
                               q_offset=S - T)
    return o.reshape(B, T, H * cfg.v_head_dim)


def mla_latent_attend(q_c, q_pe, c_kv, k_pe, layer, cache_len, *, scale):
    """The absorbed decode's attention core in jnp: the plain form of
    ``kernels.mla_decode``, and its path off the TPU. q_c (B, H, r), q_pe
    (B, H, rope) in the compute dtype; c_kv (L, B, S_max, r), k_pe (L, B,
    S_max, rope): every layer's cache. Slices out layer ``layer``, scores
    all ``S_max`` positions and masks those at or past ``cache_len``. ->
    (B, H, r) float32, the softmax-weighted sum of that layer's c_kv."""
    dt, f32 = q_c.dtype, jnp.float32
    c, pe = (jax.lax.dynamic_index_in_dim(a, layer, keepdims=False).astype(dt)
             for a in (c_kv, k_pe))
    B, S, _ = c.shape
    s = jnp.einsum("bhc,bsc->bhs", q_c, c, preferred_element_type=f32) \
        + jnp.einsum("bhr,bsr->bhs", q_pe, pe, preferred_element_type=f32)
    s = s * scale
    valid = jnp.arange(S)[None, :] < jnp.broadcast_to(
        jnp.asarray(cache_len), (B,))[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    pr = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    o_c = jnp.einsum("bhs,bsc->bhc", pr.astype(dt), c,
                     preferred_element_type=f32)
    return o_c / jnp.sum(pr, axis=-1)[..., None]


def _latent_attend(q_c, q_pe, c_kv, k_pe, layer, cache_len, *, scale):
    """:func:`mla_latent_attend`, run by the Pallas kernel where the
    program is lowered for a TPU and the kernel tiles the shapes."""
    core = functools.partial(mla_latent_attend, scale=scale)
    if not mla_decode.tileable(q_c, q_pe, c_kv, k_pe):
        return core(q_c, q_pe, c_kv, k_pe, layer, cache_len)
    return jax.lax.platform_dependent(
        q_c, q_pe, c_kv, k_pe, layer, cache_len,
        tpu=functools.partial(mla_decode.mla_decode, scale=scale),
        default=core)


def mla_attend_absorbed(p, q_nope, q_pe, latent, layer, cache_len, cfg):
    """One query token a sequence over layer ``layer`` of ``latent`` (every
    layer's c_kv (L, B, S_max, r) and k_pe (L, B, S_max, rope)), positions
    at or past ``cache_len`` masked, absorbed: compute-dtype operands,
    float32 accumulation. -> (B, 1, H * v)."""
    dt, f32 = jnp.dtype(cfg.compute_dtype), jnp.float32
    nope = cfg.qk_nope_head_dim
    w = _mla_wkv_b(p, cfg)
    q_c = jnp.einsum("bhn,chn->bhc", q_nope[:, 0].astype(dt), w[..., :nope],
                     preferred_element_type=f32).astype(dt)
    o_c = _latent_attend(q_c, q_pe[:, 0].astype(dt), *latent, layer,
                         cache_len,
                         scale=1.0 / math.sqrt(nope + cfg.qk_rope_head_dim))
    o = jnp.einsum("bhc,chv->bhv", o_c.astype(dt), w[..., nope:],
                   preferred_element_type=f32)
    return o.reshape(q_nope.shape[0], 1, -1).astype(dt)


def mla_apply(p, x, cfg, *, positions, kv_cache=None, cache_index=None,
              cache_layer=None, attn_fn=None):
    """Latent attention. Returns (out, latent): without a cache, the
    latent (c_kv (B, T, r), k_pe (B, T, rope)) of this call's tokens; with
    ``kv_cache`` (every layer's latent cache, (L, B, S_max, r) and (L, B,
    S_max, rope), with ``cache_layer`` the layer this call is), one new
    token a sequence written into that layer at ``cache_index`` and
    attended absorbed where it is stored, and the cache returned."""
    T = x.shape[1]
    dt = jnp.dtype(cfg.compute_dtype)
    q_nope, q_pe, lat = _mla_project(p, x, cfg, positions)
    if kv_cache is None:
        o = mla_attend_expanded(p, q_nope, q_pe, lat, cfg, attn_fn=attn_fn)
        return layers.dense(p["wo"], o, dtype=dt), lat
    cache = tuple(jax.lax.dynamic_update_slice(
        a, new[None].astype(a.dtype), (cache_layer, 0, cache_index, 0))
        for a, new in zip(kv_cache, lat))
    with jax.named_scope("mla.attend"):
        o = mla_attend_absorbed(p, q_nope, q_pe, cache, cache_layer,
                                cache_index + T, cfg)
    return layers.dense(p["wo"], o, dtype=dt), cache
