"""Mixture-of-Experts block, one of two layers by ``cfg.moe_dispatch``:

* ``"dropless"`` — one chip's share of an expert-parallel deployment
  (``moe_apply_held``): a float32 router over all ``num_experts``, the part
  of the result that the experts it holds give, with no token dropped, and
  the shared experts.
* otherwise — top-k softmax routing with capacity-bounded dispatch
  (``moe_apply_einsum``): GShard-style one-hot dispatch/combine einsums over
  groups of ``GROUP_SIZE`` tokens; a (token, choice) past its expert's
  capacity in a group gets nothing from that expert. Pure pjit, shards under
  any mesh; dispatch FLOPs overhead is ~(2/3)·T_group·cf/d_ff (≈4% for
  dbrx, ≈1% for grok).

Expert weights are stored (E, d, ff); the *sharding rule* (parallel/
sharding.py) decides EP (experts over 'model') vs TP (d_ff over 'model').
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import layers

GROUP_SIZE = 512        # tokens a capacity group, at most


def moe_init(key, cfg):
    if cfg.moe_dispatch == "dropless":
        return moe_init_held(key, cfg)
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    std = 1.0 / jnp.sqrt(d)
    return {
        "router": layers.truncated_normal(ks[0], (d, E), 0.02),
        "wg": layers.truncated_normal(ks[1], (E, d, ff), std),
        "wu": layers.truncated_normal(ks[2], (E, d, ff), std),
        "wd": layers.truncated_normal(ks[3], (E, ff, d), 1.0 / jnp.sqrt(ff)),
    }


def _route(p, x2d, cfg):
    """x2d: (T, d) -> (weights (T,k), experts (T,k), probs (T,E))."""
    logits = x2d.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w.astype(jnp.float32), idx, probs


def aux_load_balance_loss(probs, idx, num_experts):
    """Switch-style load-balance loss (mean fraction * mean prob * E)."""
    T = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(idx.size, 1)
    mean_prob = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(frac * mean_prob)


def _expert_ffn(p, xin, cfg, dt):
    """xin: (..., E, C, d) -> (..., E, C, d) through per-expert SwiGLU."""
    g = jnp.einsum("...ecd,edf->...ecf", xin, p["wg"].astype(dt))
    u = jnp.einsum("...ecd,edf->...ecf", xin, p["wu"].astype(dt))
    act = jax.nn.silu(g) if cfg.mlp == "swiglu" else jax.nn.gelu(g)
    return jnp.einsum("...ecf,efd->...ecd", act * u, p["wd"].astype(dt))


def moe_apply_einsum(p, x, cfg):
    """x: (B, S, d) -> (y, aux_loss). One-hot grouped dispatch."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    dt = jnp.dtype(cfg.compute_dtype)
    T = B * S
    g = min(GROUP_SIZE, T)
    n_grp = T // g
    x2 = x.reshape(T, d)
    w, idx, probs = _route(p, x2, cfg)
    aux = aux_load_balance_loss(probs, idx, E)

    cap = max(1, int(g * k / E * cfg.capacity_factor))
    xg = x2.reshape(n_grp, g, d)
    wg_ = w.reshape(n_grp, g, k)
    ig = idx.reshape(n_grp, g, k)

    # position of each (token, choice) within its expert queue, per group
    onehot = jax.nn.one_hot(ig, E, dtype=jnp.int32)            # (n,g,k,E)
    flat = onehot.reshape(n_grp, g * k, E)
    pos = jnp.cumsum(flat, axis=1) - 1                          # (n,g*k,E)
    pos = pos.reshape(n_grp, g, k, E)
    in_cap = (pos < cap) & (onehot > 0)

    # dispatch tensor (n, g, E, cap): 1 where token t goes to slot (e, c)
    # slot is zero for (token, choice, expert) triples that are not selected
    # or overflow capacity (their index is clamped to the dropped column).
    slot = jax.nn.one_hot(jnp.where(in_cap, pos, cap), cap + 1,
                          dtype=dt)[..., :cap]                  # (n,g,k,E,cap)
    disp = jnp.sum(slot, axis=2)                                # (n,g,E,cap)
    comb = jnp.sum(slot * wg_[..., None, None].astype(dt), axis=2)

    xin = jnp.einsum("ngec,ngd->necd", disp, xg.astype(dt))     # (n,E,cap,d)
    yout = _expert_ffn(p, xin, cfg, dt)                          # (n,E,cap,d)
    y = jnp.einsum("ngec,necd->ngd", comb, yout)
    return y.reshape(B, S, d).astype(x.dtype), aux


def moe_apply(p, x, cfg):
    """x: (B, S, d) -> (y, aux). A dropless layer's ``aux`` is its routed
    counts (``moe_apply_held``), a capacity layer's its load-balance
    loss."""
    if cfg.moe_dispatch == "dropless":
        return moe_apply_held(p, x, cfg)
    return moe_apply_einsum(p, x, cfg)


# ---------------------------------------------------------------------------
# One chip's share of the experts, dropless (DeepSeek-V3's MoE layer)
# ---------------------------------------------------------------------------

def moe_init_held(key, cfg):
    """The router over all ``num_experts``, the held experts, each drawn
    from its own key (a share holds the same weights as the uncut layer's
    experts of those numbers), the router's score bias and the shared
    MLP."""
    d, E = cfg.d_model, cfg.num_experts
    ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)

    def expert(k):
        kg, ku, kd = jax.random.split(k, 3)
        return {"wg": layers.truncated_normal(kg, (d, ff), 1 / math.sqrt(d)),
                "wu": layers.truncated_normal(ku, (d, ff), 1 / math.sqrt(d)),
                "wd": layers.truncated_normal(kd, (ff, d), 1 / math.sqrt(ff))}
    first = cfg.first_expert
    held = jax.random.split(ks[1], E)[first:first + cfg.experts_here]
    p = {"router": layers.truncated_normal(ks[0], (d, E), 1 / math.sqrt(d)),
         # DeepSeek-V3's e_score_correction_bias: it moves which experts
         # are picked, not their weights
         "router_bias": jnp.zeros((E,), jnp.float32),
         **jax.vmap(expert)(held)}
    if cfg.num_shared_experts:
        p["shared"] = layers.mlp_init(ks[2], cfg.mlp, d,
                                      ff * cfg.num_shared_experts)
    return p


def route_held(p, x2d, cfg):
    """x2d (T, d) -> (weights (T, k) float32, experts (T, k)) over all
    ``num_experts``, as DeepSeek-V3's router (``noaux_tc`` with one group):
    scores the sigmoid of a float32 matmul, the top k of score + bias,
    their scores normalised to sum to one and scaled by
    ``routed_scaling``."""
    score = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x2d.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(score + p["router_bias"].astype(jnp.float32),
                           cfg.experts_per_token)
    w = jnp.take_along_axis(score, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling, idx


def moe_apply_held(p, x, cfg):
    """x: (B, S, d) -> (y, counts). ``y``: what the held experts give each
    token (each expert weighted by the token's routing weight for it, zero
    where it was not picked), plus the shared MLP. Every held expert
    computes every token, so there is no capacity and no token is
    dropped; in decode each held expert's weights are read once a step
    however the tokens fall. ``counts`` (held,) int32: the (token, choice)
    pairs routed to each held expert."""
    B, S, d = x.shape
    dt = jnp.dtype(cfg.compute_dtype)
    x2 = x.reshape(B * S, d)
    with jax.named_scope("moe.route"):
        w, idx = route_held(p, x2, cfg)
        held = cfg.first_expert + jnp.arange(cfg.experts_here)
        hit = idx[:, :, None] == held                       # (T, k, held)
        gate = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)
        counts = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("moe.experts"):
        xe = x2.astype(dt)
        g = jnp.einsum("td,edf->etf", xe, p["wg"].astype(dt))
        u = jnp.einsum("td,edf->etf", xe, p["wu"].astype(dt))
        act = jax.nn.silu(g) if cfg.mlp == "swiglu" else jax.nn.gelu(g)
        ye = jnp.einsum("etf,efd->etd", act * u, p["wd"].astype(dt))
        y = jnp.sum(ye.astype(jnp.float32) * gate.T[:, :, None], axis=0)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + layers.apply_mlp(cfg.mlp, p["shared"], x2,
                                     dtype=dt).astype(jnp.float32)
    return y.reshape(B, S, d).astype(x.dtype), counts
