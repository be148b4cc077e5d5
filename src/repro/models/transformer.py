"""LM-family model assembly: dense / MoE / VLM / hybrid(Zamba2) / SSM(RWKV6).

All stacks scan over layers (params carry a leading L dim) with a
configurable remat policy. Decode carries every layer's attention cache
through the layer scan and writes each layer's new position in place
(``attention.attn_apply`` with ``cache_layer``); recurrent states are
scanned as xs/ys.

Public API (used by launch/, train/, serve/):
    init_lm(cfg, key)                       -> params
    apply_lm(cfg, params, tokens, ...)      -> (hidden, aux)        train fwd
    prefill_lm(cfg, params, tokens, ...)    -> (hidden, cache)
    decode_lm(cfg, params, cache, tokens)   -> (logits, cache)      1 new token
        (cfg.reports_routing: and the step's routed counts, see decode_lm)
    init_cache(cfg, batch, max_seq)         -> cache pytree
    unembed(cfg, params, hidden)            -> logits
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba, moe, rwkv
from repro.parallel import ctx as pctx

REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


# ---------------------------------------------------------------------------
# Per-layer blocks
# ---------------------------------------------------------------------------

def _block_init(key, cfg, dense: bool = False):
    """One decoder layer; ``dense``: a dense MLP in an MoE model (its
    leading layers)."""
    ks = jax.random.split(key, 4)
    p = {
        "attn_norm": layers.norm_init(cfg.norm, cfg.d_model),
        "attn": attention.attn_init(ks[0], cfg),
        "mlp_norm": layers.norm_init(cfg.norm, cfg.d_model),
    }
    if cfg.family == "moe" and not dense:
        p["moe"] = moe.moe_init(ks[1], cfg)
    else:
        p["mlp"] = layers.mlp_init(ks[1], cfg.mlp, cfg.d_model, cfg.d_ff, bias=cfg.bias)
    return p


def _block_apply(p, x, cfg, *, positions, kv=None, cache_index=None,
                 cache_layer=None, attn_fn=None):
    """-> (x, new_kv, aux): ``aux`` is an MoE layer's load-balance loss,
    or a dropless one's routed counts (``moe.moe_apply_held``)."""
    h = layers.apply_norm(cfg.norm, p["attn_norm"], x)
    h, new_kv = attention.attn_apply(
        p["attn"], h, cfg, positions=positions, kv_cache=kv,
        cache_index=cache_index, cache_layer=cache_layer, attn_fn=attn_fn)
    x = x + h
    h = layers.apply_norm(cfg.norm, p["mlp_norm"], x)
    if "moe" in p:
        h, aux = moe.moe_apply(p["moe"], h, cfg)
    else:
        h, aux = layers.apply_mlp(cfg.mlp, p["mlp"], h), 0.0
    return x + h, new_kv, aux


def _mamba_layer_init(key, cfg):
    return {"norm": layers.norm_init(cfg.norm, cfg.d_model),
            "mamba": mamba.mamba_init(key, cfg)}


def _mamba_layer_apply(p, x, cfg, state=None):
    h = layers.apply_norm(cfg.norm, p["norm"], x)
    h, new_state = mamba.mamba_apply(p["mamba"], h, cfg, state=state)
    return x + h, new_state


def _rwkv_layer_init(key, cfg):
    return {"block": rwkv.rwkv_init(key, cfg),
            "ln1": layers.norm_init("layernorm", cfg.d_model),
            "ln2": layers.norm_init("layernorm", cfg.d_model)}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(fn, key, n):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_lm(cfg, key):
    ks = jax.random.split(key, 8)
    params = {"emb": layers.embed_init(ks[0], cfg.vocab_size, cfg.d_model),
              "final_norm": layers.norm_init(cfg.norm, cfg.d_model)}
    if not cfg.tie_embeddings:
        params["unembed"] = layers.dense_init(ks[1], cfg.d_model, cfg.vocab_size)

    if cfg.family in ("dense", "moe", "vlm"):
        n_dense = cfg.first_dense_layers
        params["blocks"] = _stack_init(partial(_block_init, cfg=cfg), ks[2],
                                       cfg.num_layers - n_dense)
        if n_dense:
            params["dense_blocks"] = _stack_init(
                partial(_block_init, cfg=cfg, dense=True), ks[5], n_dense)
    elif cfg.family == "ssm":
        params["blocks"] = _stack_init(partial(_rwkv_layer_init, cfg=cfg), ks[2], cfg.num_layers)
        params["ln0"] = layers.norm_init("layernorm", cfg.d_model)
    elif cfg.family == "hybrid":
        params["prologue"] = _stack_init(partial(_mamba_layer_init, cfg=cfg),
                                         ks[2], cfg.hybrid_prologue)
        params["groups"] = jax.vmap(
            lambda k: _stack_init(partial(_mamba_layer_init, cfg=cfg), k,
                                  cfg.hybrid_mamba_per_group)
        )(jax.random.split(ks[3], cfg.hybrid_groups))
        params["shared_attn"] = _block_init(ks[4], cfg)  # ONE weight set, reused
    else:
        raise ValueError(cfg.family)
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16):
    hd, hkv = cfg.head_dim, cfg.num_kv_heads
    if cfg.kv_lora_rank:    # the latent: c_kv and k_pe per layer
        cache = {"idx": jnp.zeros((), jnp.int32)}
        n_dense = cfg.first_dense_layers
        for stack, n in (("dense_blocks", n_dense),
                         ("blocks", cfg.num_layers - n_dense)):
            c, pe = _cache_names(cfg, stack)
            if n:
                cache[c] = jnp.zeros((n, batch, max_seq, cfg.kv_lora_rank),
                                     dtype)
                cache[pe] = jnp.zeros(
                    (n, batch, max_seq, cfg.qk_rope_head_dim), dtype)
        return cache
    if cfg.family in ("dense", "moe", "vlm"):
        return {
            "k": jnp.zeros((cfg.num_layers, batch, max_seq, hkv, hd), dtype),
            "v": jnp.zeros((cfg.num_layers, batch, max_seq, hkv, hd), dtype),
            "idx": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "ssm":
        st = rwkv.rwkv_state_init(cfg, batch)
        return {"layers": jax.tree.map(
                    lambda t: jnp.broadcast_to(t, (cfg.num_layers,) + t.shape), st),
                "idx": jnp.zeros((), jnp.int32)}
    if cfg.family == "hybrid":
        st = mamba.mamba_state_init(cfg, batch)
        stack = lambda t, n: jnp.broadcast_to(t, (n,) + t.shape)
        return {
            "prologue": jax.tree.map(lambda t: stack(t, cfg.hybrid_prologue), st),
            "groups": jax.tree.map(
                lambda t: stack(stack(t, cfg.hybrid_mamba_per_group), cfg.hybrid_groups), st),
            "attn_k": jnp.zeros((cfg.hybrid_groups, batch, max_seq, hkv, hd), dtype),
            "attn_v": jnp.zeros((cfg.hybrid_groups, batch, max_seq, hkv, hd), dtype),
            "idx": jnp.zeros((), jnp.int32),
        }
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, params, tokens, embeds):
    x = layers.embed(params["emb"], tokens, dtype=jnp.dtype(cfg.compute_dtype))
    if embeds is not None:  # vlm/frontend stub: precomputed prefix embeddings
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    return x


def _layer_stacks(params):
    """The stacked layers in order: an MoE model's leading dense layers
    first."""
    return [params[k] for k in ("dense_blocks", "blocks") if k in params]


def _cache_names(cfg, stack: str):
    """The cache arrays of the layers of ``stack`` ("blocks", or an MoE
    model's leading "dense_blocks", which keep arrays of their own, so that
    no array is both inside the decode step's layer loop and outside it)."""
    names = ("c_kv", "k_pe") if cfg.kv_lora_rank else ("k", "v")
    return tuple(("dense_" + n if stack == "dense_blocks" else n)
                 for n in names)


def _scan_blocks(cfg, body, x, xs, remat: str):
    if remat != "none":
        body = jax.checkpoint(body, policy=REMAT_POLICIES[remat],
                              prevent_cse=False)
    return jax.lax.scan(body, x, xs)


def apply_lm(cfg, params, tokens, *, embeds=None, attn_fn=None,
             remat: str = "full", collect_kv: bool = False):
    """Training/prefill forward. Returns (hidden, aux, kv_stack|None)."""
    x = _embed_inputs(cfg, params, tokens, embeds)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, blk):
            h, aux = carry
            h, kv, a = _block_apply(blk, h, cfg, positions=positions,
                                    attn_fn=attn_fn)
            if cfg.moe_dispatch == "dropless":
                a = 0.0                 # routed counts; no balance loss
            return (pctx.constrain(h), aux + a), (kv if collect_kv else None)
        carry, kvs = (x, 0.0), []
        for stack in _layer_stacks(params):
            carry, kv = _scan_blocks(cfg, body, carry, stack, remat)
            kvs.append(kv)
        (x, aux) = carry
        kvs = kvs[0] if len(kvs) == 1 else kvs  # one stack: (k, v) as ever
    elif cfg.family == "ssm":
        x = layers.apply_norm("layernorm", params["ln0"], x)
        st0 = rwkv.rwkv_state_init(cfg, B)

        def body(h, blk):
            h, st = rwkv.rwkv_block(blk["block"], h, cfg,
                                    {"ln1": blk["ln1"], "ln2": blk["ln2"]}, state=st0)
            return pctx.constrain(h), (st if collect_kv else None)
        x, kvs = _scan_blocks(cfg, body, x, params["blocks"], remat)
        aux = 0.0
    elif cfg.family == "hybrid":
        st0 = mamba.mamba_state_init(cfg, B)

        def mbody(h, blk):
            h, st = _mamba_layer_apply(blk, h, cfg, state=st0)
            return pctx.constrain(h), (st if collect_kv else None)
        x, pro_sts = _scan_blocks(cfg, mbody, x, params["prologue"], remat)
        shared = params["shared_attn"]

        def gbody(h, blk):
            h, msts = _scan_blocks(cfg, mbody, h, blk,
                                   "full" if remat != "none" else "none")
            h, kv, _ = _block_apply(shared, h, cfg, positions=positions,
                                    attn_fn=attn_fn)
            return pctx.constrain(h), ((msts, kv) if collect_kv else None)
        x, grp = _scan_blocks(cfg, gbody, x, params["groups"], remat)
        kvs = (pro_sts, grp)
        aux = 0.0
    else:
        raise ValueError(cfg.family)

    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    return x, aux, kvs


def unembed(cfg, params, hidden):
    dt = jnp.dtype(cfg.compute_dtype)
    if cfg.tie_embeddings:
        return hidden.astype(dt) @ params["emb"]["table"].T.astype(dt)
    return layers.dense(params["unembed"], hidden, dtype=dt)


def prefill_lm(cfg, params, tokens, *, embeds=None, attn_fn=None,
               max_seq: Optional[int] = None, remat: str = "full"):
    """Forward + build decode cache. Returns (hidden, cache)."""
    hidden, _, kvs = apply_lm(cfg, params, tokens, embeds=embeds,
                              attn_fn=attn_fn, remat=remat, collect_kv=True)
    B = tokens.shape[0]
    S = hidden.shape[1]
    max_seq = max_seq or S
    cache = init_cache(cfg, B, max_seq, dtype=jnp.dtype(cfg.compute_dtype))
    if cfg.family in ("dense", "moe", "vlm"):
        # (L,B,S,hkv,hd) K and V, or latent attention's c_kv and k_pe, of
        # each stack of layers
        stacks = [k for k in ("dense_blocks", "blocks") if k in params]
        for stack, kv in zip(stacks, kvs if len(stacks) > 1 else [kvs]):
            for name, new in zip(_cache_names(cfg, stack), kv):
                cache[name] = jax.lax.dynamic_update_slice_in_dim(
                    cache[name], new.astype(cache[name].dtype), 0, axis=2)
    elif cfg.family == "ssm":
        cache["layers"] = kvs
    elif cfg.family == "hybrid":
        pro_sts, (msts, kv) = kvs
        cache["prologue"] = pro_sts
        cache["groups"] = msts
        k, v = kv
        cache["attn_k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["attn_k"], k.astype(cache["attn_k"].dtype), 0, axis=2)
        cache["attn_v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["attn_v"], v.astype(cache["attn_v"].dtype), 0, axis=2)
    cache["idx"] = jnp.asarray(S, jnp.int32)
    return hidden, cache


def decode_lm(cfg, params, cache, tokens):
    """One decode step. tokens: (B, 1). Returns (logits, new_cache), and
    where ``cfg.reports_routing`` also the step's routed counts: (MoE layers, held experts) int32, the (token,
    choice) pairs each held expert was given in each layer."""
    x = _embed_inputs(cfg, params, tokens, None)
    idx = cache["idx"]
    positions = idx + jnp.zeros((1, 1), jnp.int32)
    counts = None

    if cfg.family in ("dense", "moe", "vlm"):
        # the stacked cache rides in the carry, so each layer writes its one
        # new position in place; scanned as xs/ys, every layer's K/V would
        # be sliced out and written back whole
        def body(carry, blk):
            h, kv, layer = carry
            h, kv, a = _block_apply(blk, h, cfg, positions=positions,
                                    kv=kv, cache_index=idx,
                                    cache_layer=layer)
            return (pctx.constrain(h, "residual_dec"), kv, layer + 1), \
                (a if cfg.reports_routing else None)
        # an MoE model's few leading dense layers are unrolled, on arrays
        # of their own: in a loop of their own, or on arrays of the main
        # loop, the cache would be laid out twice and copied whole between
        written = {}
        if "dense_blocks" in params:
            names = _cache_names(cfg, "dense_blocks")
            carry = (x, tuple(cache[n] for n in names), jnp.int32(0))
            for i in range(cfg.first_dense_layers):
                carry, _ = body(carry, jax.tree.map(lambda a: a[i],
                                                    params["dense_blocks"]))
            x, kv, _ = carry
            written.update(zip(names, kv))
        names = _cache_names(cfg, "blocks")
        (x, kv, _), counts = jax.lax.scan(
            body, (x, tuple(cache[n] for n in names), jnp.int32(0)),
            params["blocks"])
        written.update(zip(names, kv))
        new_cache = dict(cache, **written, idx=idx + 1)
    elif cfg.family == "ssm":
        x = layers.apply_norm("layernorm", params["ln0"], x)

        def body(h, blk_st):
            blk, st = blk_st
            h, st2 = rwkv.rwkv_block(blk["block"], h, cfg,
                                     {"ln1": blk["ln1"], "ln2": blk["ln2"]}, state=st)
            return pctx.constrain(h, "residual_dec"), st2
        x, sts = jax.lax.scan(body, x, (params["blocks"], cache["layers"]))
        new_cache = dict(cache, layers=sts, idx=idx + 1)
    elif cfg.family == "hybrid":
        def mbody(h, blk_st):
            blk, st = blk_st
            h, st2 = _mamba_layer_apply(blk, h, cfg, state=st)
            return pctx.constrain(h, "residual_dec"), st2
        x, pro_sts = jax.lax.scan(mbody, x, (params["prologue"], cache["prologue"]))
        shared = params["shared_attn"]

        # the groups' attention caches ride in the carry, as the dense
        # decode's do; the mamba states are small and stay xs/ys
        def gbody(carry, inp):
            h, kv, g = carry
            blk, msts = inp
            h, msts2 = jax.lax.scan(mbody, h, (blk, msts))
            h, kv, _ = _block_apply(shared, h, cfg, positions=positions,
                                    kv=kv, cache_index=idx, cache_layer=g)
            return (pctx.constrain(h, "residual_dec"), kv, g + 1), msts2
        (x, (ks, vs), _), gsts = jax.lax.scan(
            gbody, (x, (cache["attn_k"], cache["attn_v"]), jnp.int32(0)),
            (params["groups"], cache["groups"]))
        new_cache = dict(cache, prologue=pro_sts, groups=gsts,
                         attn_k=ks, attn_v=vs, idx=idx + 1)
    else:
        raise ValueError(cfg.family)

    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    if cfg.reports_routing:
        return unembed(cfg, params, x), new_cache, counts
    return unembed(cfg, params, x), new_cache
