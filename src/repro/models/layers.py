"""Primitive layers: norms, linears, rotary embeddings, MLP blocks.

Parameters are plain pytrees (nested dicts of jnp arrays); every layer is a
pair of ``*_init(key, ...) -> params`` and a pure apply function. Compute
follows cfg.compute_dtype (bf16 by default) with fp32 norms/softmax.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def truncated_normal(key, shape, stddev, dtype=jnp.float32):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32).astype(dtype)


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False, dtype=jnp.float32):
    stddev = 1.0 / math.sqrt(d_in)
    p = {"w": truncated_normal(key, (d_in, d_out), stddev, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, *, dtype=jnp.bfloat16):
    y = x.astype(dtype) @ p["w"].astype(dtype)
    if "b" in p:
        y = y + p["b"].astype(dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(kind: str, d: int):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
    if kind == "nonparam_ln":  # OLMo: non-parametric LayerNorm
        return {}
    raise ValueError(kind)


def apply_norm(kind: str, p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        y = y * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        if kind == "layernorm":
            y = y * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (llama-style half rotation)
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float, *, interleaved: bool = False):
    """x: (..., seq, heads, head_dim), positions: (..., seq).

    ``interleaved``: the pairs rotated together are adjacent features
    (2i, 2i + 1), as DeepSeek-V2/V3 store them; the result has the
    even features first, then the odd ones (their reference code's
    order). Otherwise features i and i + head_dim / 2 pair up."""
    hd = x.shape[-1]
    half = hd // 2
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(ang)[..., :, None, :]   # broadcast over heads
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP blocks
# ---------------------------------------------------------------------------

def mlp_init(key, kind: str, d: int, d_ff: int, *, bias: bool = False):
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "wg": dense_init(ks[0], d, d_ff, bias=bias),
            "wu": dense_init(ks[1], d, d_ff, bias=bias),
            "wd": dense_init(ks[2], d_ff, d, bias=bias),
        }
    if kind == "gelu_mlp":
        return {
            "wu": dense_init(ks[0], d, d_ff, bias=bias),
            "wd": dense_init(ks[1], d_ff, d, bias=bias),
        }
    raise ValueError(kind)


def apply_mlp(kind: str, p, x, *, dtype=jnp.bfloat16):
    if kind in ("swiglu", "geglu"):
        g = dense(p["wg"], x, dtype=dtype)
        act = jax.nn.silu(g) if kind == "swiglu" else jax.nn.gelu(g)
        h = act * dense(p["wu"], x, dtype=dtype)
        return dense(p["wd"], h, dtype=dtype)
    h = jax.nn.gelu(dense(p["wu"], x, dtype=dtype))
    return dense(p["wd"], h, dtype=dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d: int):
    return {"table": truncated_normal(key, (vocab, d), 1.0 / math.sqrt(d))}


def embed(p, tokens, *, dtype=jnp.bfloat16):
    return jnp.take(p["table"].astype(dtype), tokens, axis=0)
