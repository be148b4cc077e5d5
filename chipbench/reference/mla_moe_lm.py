"""Plain float32 forward of a DeepSeek-V3-style LM (``model_type``
``deepseek_v3``: latent attention, routed and shared experts, leading dense
layers), independent of the program under test: it imports nothing of it
and takes nothing it made.

It follows the published layer equations, with every size read from the
configuration file:

- latent attention without a query LoRA: ``q = x W_q`` split into
  ``q_nope`` and ``q_pe``; ``[c_kv | k_pe] = x W_kv_a``, ``c_kv`` through
  its own RMSNorm (eps 1e-6); ``[k_nope | v] = c_kv W_kv_b`` per head; RoPE
  on ``q_pe`` and on the one ``k_pe`` all heads share, in DeepSeek-V3's
  convention (adjacent features rotate together, reordered evens first);
  softmax scale ``(nope + rope)^-0.5``; then ``W_o``. Keys and values are
  expanded: no cache and no absorption.
- the first ``first_k_dense_replace`` layers: a dense SwiGLU MLP;
- the others: a float32 router ``s = sigmoid(x W_g)`` over all the
  published experts, the top ``num_experts_per_tok`` of ``s + bias`` (the
  bias is zeros), their scores normalised to sum to one and scaled by
  ``routed_scaling_factor``; the part of the result that the held experts
  give (``n_routed_experts`` of them, from ``deployment.held_experts``),
  nothing dropped; plus the shared SwiGLU MLP of width
  ``moe_intermediate_size * n_shared_experts``;
- RMSNorms of ``rms_norm_eps``; an untied head.

Weights are random. ``init_weights`` draws them from the seed by the same
scheme as the system under test (a truncated normal over (-2, 2) scaled by
1/sqrt(fan_in) per matrix, keys split per matrix, per layer, per module and
per expert, each held expert drawn from the key of its number among all the
published experts, norm scales one, the router's bias zero), so that the
same seed gives the same weights on both sides; then rounds them to the
served dtype.

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
LATENT_NORM_EPS = 1e-6


def _sizes(conf) -> dict:
    """The numbers the forward needs, flat and hashable."""
    dep = conf["deployment"]
    return {"d": conf["hidden_size"], "heads": conf["num_attention_heads"],
            "layers": conf["num_hidden_layers"],
            "dense_layers": conf["first_k_dense_replace"],
            "ff": conf["intermediate_size"],
            "moe_ff": conf["moe_intermediate_size"],
            "shared": conf["n_shared_experts"],
            "experts": dep["published_n_routed_experts"],
            "first": dep["held_experts"][0], "held": conf["n_routed_experts"],
            "top_k": conf["num_experts_per_tok"],
            "scale": conf["routed_scaling_factor"],
            "rank": conf["kv_lora_rank"], "nope": conf["qk_nope_head_dim"],
            "rope": conf["qk_rope_head_dim"], "v": conf["v_head_dim"],
            "vocab": conf["vocab_size"], "theta": float(conf["rope_theta"]),
            "eps": conf["rms_norm_eps"]}


def _tn(key, shape, fan_in):
    return (1.0 / math.sqrt(fan_in)) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)


def _mlp_init(key, d, ff):
    kg, ku, kd = jax.random.split(key, 3)
    return {"wg": _tn(kg, (d, ff), d), "wu": _tn(ku, (d, ff), d),
            "wd": _tn(kd, (ff, d), ff)}


def _attn_init(key, z):
    d, h, r = z["d"], z["heads"], z["rank"]
    ka = jax.random.split(key, 4)
    return {"wq": _tn(ka[0], (d, h * (z["nope"] + z["rope"])), d),
            "wkv_a": _tn(ka[1], (d, r + z["rope"]), d),
            "kv_norm": jnp.ones((r,)),
            "wkv_b": _tn(ka[2], (r, h * (z["nope"] + z["v"])), r),
            "wo": _tn(ka[3], (h * z["v"], d), h * z["v"])}


def _layer_init(key, z, moe: bool):
    d = z["d"]
    k_attn, k_mlp = jax.random.split(key, 4)[:2]
    w = {"attn": _attn_init(k_attn, z), "attn_norm": jnp.ones((d,)),
         "mlp_norm": jnp.ones((d,))}
    if not moe:
        w["mlp"] = _mlp_init(k_mlp, d, z["ff"])
        return w
    kr, ke, ks = jax.random.split(k_mlp, 3)
    mine = jax.random.split(ke, z["experts"])[z["first"]:z["first"] + z["held"]]
    w["router"] = _tn(kr, (d, z["experts"]), d)
    w["router_bias"] = jnp.zeros((z["experts"],))
    w["experts"] = jax.vmap(partial(_mlp_init, d=d, ff=z["moe_ff"]))(mine)
    w["shared"] = _mlp_init(ks, d, z["moe_ff"] * z["shared"])
    return w


def init_weights(conf, seed: int, dtype=jnp.bfloat16):
    """All weights, made on the device by one program, in ``dtype``."""
    z = _sizes(conf)
    n_dense = z["dense_layers"]

    @jax.jit
    def init(key):
        ks = jax.random.split(key, 8)
        w = {"emb": _tn(ks[0], (z["vocab"], z["d"]), z["d"]),
             "head": _tn(ks[1], (z["d"], z["vocab"]), z["d"]),
             "final_norm": jnp.ones((z["d"],)),
             "moe_layers": jax.vmap(partial(_layer_init, z=z, moe=True))(
                 jax.random.split(ks[2], z["layers"] - n_dense)),
             "dense_layers": jax.vmap(partial(_layer_init, z=z, moe=False))(
                 jax.random.split(ks[5], n_dense))}
        return jax.tree.map(lambda x: x.astype(dtype), w)
    return init(jax.random.PRNGKey(seed))


def f32_linear(x, w):
    return jnp.einsum("...i,io->...o", x, w.astype(jnp.float32),
                      precision=HI, preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    y = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, T, heads, dim), position t = index along T. DeepSeek-V3's
    apply_rotary_pos_emb: view the features as (dim/2, 2) pairs, transpose
    to evens then odds, rotate halves."""
    B, T, h, dim = x.shape
    x = x.reshape(B, T, h, dim // 2, 2).swapaxes(-1, -2).reshape(B, T, h, dim)
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _mlp(linear, x, w):
    return linear(jax.nn.silu(linear(x, w["wg"])) * linear(x, w["wu"]),
                  w["wd"])


def _attention(z, linear, x, w):
    B, T, _ = x.shape
    h, r, nope, rope, vd = z["heads"], z["rank"], z["nope"], z["rope"], z["v"]
    q = linear(x, w["wq"]).reshape(B, T, h, nope + rope)
    kv_a = linear(x, w["wkv_a"])
    c_kv = _rms(kv_a[..., :r], w["kv_norm"], LATENT_NORM_EPS)
    kv = linear(c_kv, w["wkv_b"]).reshape(B, T, h, nope + vd)
    q_pe = _rope(q[..., nope:], z["theta"])
    k_pe = _rope(kv_a[:, :, None, r:], z["theta"])
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, h, rope))], -1)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) \
        / math.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, NEG_INF)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), kv[..., nope:],
                   precision=HI)
    return linear(o.reshape(B, T, h * vd), w["wo"])


def _moe(z, linear, x, w):
    score = jax.nn.sigmoid(linear(x, w["router"]))
    _, idx = lax.top_k(score + w["router_bias"].astype(jnp.float32),
                       z["top_k"])
    picked = jnp.take_along_axis(score, idx, -1)
    picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * z["scale"]
    y = _mlp(linear, x, w["shared"])
    for j in range(z["held"]):
        gate = jnp.sum(jnp.where(idx == z["first"] + j, picked, 0.0), -1)
        expert = jax.tree.map(lambda a: a[j], w["experts"])
        y = y + gate[..., None] * _mlp(linear, x, expert)
    return y


def _layer(z, linear, moe, x, w):
    x = x + _attention(z, linear, _rms(x, w["attn_norm"], z["eps"]),
                       w["attn"])
    hn = _rms(x, w["mlp_norm"], z["eps"])
    return x + (_moe(z, linear, hn, w) if moe else _mlp(linear, hn, w["mlp"]))


@partial(jax.jit, static_argnums=(0, 1))
def _forward(sizes, linear, weights, tokens, read_pos):
    """Logits at ``read_pos`` (B, n) of each sequence in ``tokens`` (B, T)."""
    z = dict(sizes)
    x = jnp.take(weights["emb"], tokens, axis=0).astype(jnp.float32)
    for moe, stack in ((False, "dense_layers"), (True, "moe_layers")):
        def body(x, w, moe=moe):
            return _layer(z, linear, moe, x, w), None
        x, _ = lax.scan(body, x, weights[stack])
    x = _rms(x, weights["final_norm"], z["eps"])
    x = jnp.take_along_axis(x, read_pos[..., None], axis=1)
    return linear(x, weights["head"])


def logits(conf, weights, tokens, read_pos, *, linear=f32_linear):
    """float32 logits (B, n, V) at positions ``read_pos`` (B, n) of the
    causal forward over ``tokens`` (B, T)."""
    return _forward(tuple(sorted(_sizes(conf).items())), linear, weights,
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
