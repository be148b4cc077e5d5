"""Plain float32 forward of a dense decoder-only LM, independent of the
program under test: it imports nothing of it and takes nothing it made.

It follows the published architecture of the configurations it serves
(Qwen2: RMSNorm, QKV bias, GQA, RoPE theta 1e6; OLMo: non-parametric
LayerNorm, MHA, RoPE theta 1e4; both SwiGLU and tied embeddings), with
every size read from the configuration file.

Weights are random. ``init_weights`` draws them from the seed by the same
scheme as the system under test (a truncated normal over (-2, 2) scaled by
1/sqrt(fan_in) per matrix, keys split per matrix, per layer and per
module, biases zero, norm scales one), so that the same seed gives the
same weights on both sides; then rounds them to the served dtype.

Every matmul runs at ``Precision.HIGHEST`` with float32 operands and
accumulation. ``linear`` is the one place a matmul of a weight happens,
so a control can put a lower precision there.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NEG_INF = -1e30


def _dims(conf):
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return d, h, conf["num_key_value_heads"], conf.get("head_dim") or d // h


def _eps(conf) -> float:
    return conf.get("rms_norm_eps", conf.get("layer_norm_eps", 1e-5))


def _tn(key, shape, fan_in):
    return (1.0 / math.sqrt(fan_in)) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)


def _layer_init(key, conf):
    d, h, hkv, hd = _dims(conf)
    ff = conf["intermediate_size"]
    k_attn, k_mlp = jax.random.split(key, 4)[:2]
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    w = {"wq": _tn(ka[0], (d, h * hd), d),
         "wk": _tn(ka[1], (d, hkv * hd), d),
         "wv": _tn(ka[2], (d, hkv * hd), d),
         "wo": _tn(ka[3], (h * hd, d), h * hd),
         "wg": _tn(km[0], (d, ff), d),
         "wu": _tn(km[1], (d, ff), d),
         "wd": _tn(km[2], (ff, d), ff)}
    if conf.get("qkv_bias"):
        w.update(bq=jnp.zeros((h * hd,)), bk=jnp.zeros((hkv * hd,)),
                 bv=jnp.zeros((hkv * hd,)))
    if conf["norm"] == "rmsnorm":
        w.update(attn_norm=jnp.ones((d,)), mlp_norm=jnp.ones((d,)))
    return w


def init_weights(conf, seed: int, dtype=jnp.bfloat16):
    """All weights, made on the device by one program, in ``dtype``."""
    if not conf["tie_word_embeddings"]:
        raise NotImplementedError("untied unembedding")
    d, v, n = conf["hidden_size"], conf["vocab_size"], \
        conf["num_hidden_layers"]

    @jax.jit
    def init(key):
        ks = jax.random.split(key, 8)
        w = {"emb": _tn(ks[0], (v, d), d),
             "layers": jax.vmap(partial(_layer_init, conf=conf))(
                 jax.random.split(ks[2], n))}
        if conf["norm"] == "rmsnorm":
            w["final_norm"] = jnp.ones((d,))
        return jax.tree.map(lambda x: x.astype(dtype), w)
    return init(jax.random.PRNGKey(seed))


def f32_linear(x, w):
    return jnp.einsum("...i,io->...o", x, w.astype(jnp.float32),
                      precision=HI, preferred_element_type=jnp.float32)


def _norm(conf, x, scale):
    eps = _eps(conf)
    if conf["norm"] == "rmsnorm":
        y = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * scale.astype(jnp.float32)
    if conf["norm"] == "nonparam_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + eps)
    raise ValueError(conf["norm"])


def _rope(x, theta):
    """x: (B, T, heads, hd), position t = index along T; rotate halves."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(conf, linear, x, w):
    d, h, hkv, hd = _dims(conf)
    B, T, _ = x.shape
    f32 = lambda a: a.astype(jnp.float32)
    hn = _norm(conf, x, w.get("attn_norm"))
    q = linear(hn, w["wq"])
    k = linear(hn, w["wk"])
    v = linear(hn, w["wv"])
    if conf.get("qkv_bias"):
        q, k, v = q + f32(w["bq"]), k + f32(w["bk"]), v + f32(w["bv"])
    q = _rope(q.reshape(B, T, h, hd), conf["rope_theta"])
    k = _rope(k.reshape(B, T, hkv, hd), conf["rope_theta"])
    v = v.reshape(B, T, hkv, hd)
    g = h // hkv
    q = q.reshape(B, T, hkv, g, hd) / math.sqrt(hd)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k, precision=HI)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v, precision=HI)
    x = x + linear(o.reshape(B, T, h * hd), w["wo"])
    hn = _norm(conf, x, w.get("mlp_norm"))
    m = jax.nn.silu(linear(hn, w["wg"])) * linear(hn, w["wu"])
    return x + linear(m, w["wd"])


@partial(jax.jit, static_argnums=(0, 1))
def _forward(conf_items, linear, weights, tokens, read_pos):
    """Logits at ``read_pos`` (B, n) of each sequence in ``tokens`` (B, T)."""
    conf = dict(conf_items)
    x = jnp.take(weights["emb"], tokens, axis=0).astype(jnp.float32)

    def body(x, w):
        return _layer(conf, linear, x, w), None
    x, _ = lax.scan(body, x, weights["layers"])
    x = _norm(conf, x, weights.get("final_norm"))
    x = jnp.take_along_axis(x, read_pos[..., None], axis=1)
    return linear(x, weights["emb"].T)


def _hashable(conf):
    return tuple(sorted((k, v) for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool))))


def logits(conf, weights, tokens, read_pos, *, linear=f32_linear):
    """float32 logits (B, n, V) at positions ``read_pos`` (B, n) of the
    causal forward over ``tokens`` (B, T)."""
    return _forward(_hashable(conf), linear, weights,
                    jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(read_pos, jnp.int32))


def fp8_linear(x, w):
    """The control's matmul: both operands rounded to float8 e4m3 with one
    scale per tensor (amax / 448), products accumulated in float32."""
    def q(a):
        a = a.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s
    xq, sx = q(x)
    wq, sw = q(w)
    return jnp.einsum("...i,io->...o", xq, wq, precision=HI,
                      preferred_element_type=jnp.float32) * (sx * sw)
