"""Sharding rules: parameter/optimizer/activation partitioning.

Strategy (DESIGN.md §7):
  * ``model`` axis = tensor parallel (heads, d_ff, vocab, experts-or-ff);
  * ``data`` (+ ``pod`` when present) = batch DP **and** FSDP-style sharding
    of parameters/optimizer state (per-layer all-gather inside the scan);
  * MoE expert weights choose EP (experts over 'model') when E divides the
    axis, else TP within experts — switchable for §Perf experiments;
  * KV caches shard heads over 'model'; long-context (batch 1) caches shard
    the *sequence* over 'data' (SP) and merge with distributed LSE.

Rules are path-pattern based over the param pytree; stacked scan layers
(leading L dim) are detected by path prefix and get PartitionSpec(None, ...).
"""
from __future__ import annotations

import re
from typing import Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def fsdp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


_STACKED = re.compile(
    r"(^|/)(blocks|dense_blocks|groups|prologue|enc_blocks|dec_blocks)(/|$)")
_EXTRA_STACK = re.compile(r"(^|/)groups(/|$)")   # zamba groups: (G, M, ...)


def _rule(path: str, ndim: int, cfg, moe_sharding: str) -> Tuple:
    """Spec for the unstacked (per-layer) leaf."""
    f = "F"   # placeholder: fsdp axes
    t = "model"

    def last(name):
        return path.endswith(name) or path.endswith(name + "/w")

    # --- MoE expert tensors (E, d, ff) / (E, ff, d); a dropless layer's
    # shared MLP is a dense MLP
    if "/moe/shared/" in path:
        return (t, f) if last("wd") else (f, t)
    if "/moe/" in path:
        if last("router"):
            return (f, None)
        if last("router_bias"):
            return (None,)
        ep = moe_sharding == "ep"
        if last("wg") or last("wu"):
            return (t, f, None) if ep else (None, f, t)
        if last("wd"):
            return (t, None, f) if ep else (None, t, f)

    # --- latent attention: W_kv_a to the latent (replicated), W_kv_b from it
    if re.search(r"/attn/wkv_a/w$", path):
        return (f, None)
    if re.search(r"/attn/wkv_b/w$", path):
        return (None, t)

    # --- attention projections
    if re.search(r"/(attn|xattn)/w[qkv]/w$", path):
        return (f, t)
    if re.search(r"/(attn|xattn)/w[qkv]/b$", path):
        return (t,)
    if re.search(r"/(attn|xattn)/wo/w$", path):
        return (t, f)
    if re.search(r"/(attn|xattn)/wo/b$", path):
        return (None,)

    # --- dense mlp
    if re.search(r"/mlp/w[gu]/w$", path):
        return (f, t)
    if re.search(r"/mlp/w[gu]/b$", path):
        return (t,)
    if re.search(r"/mlp/wd/w$", path):
        return (t, f)
    if re.search(r"/mlp/wd/b$", path):
        return (None,)

    # --- mamba
    if "/mamba/" in path:
        if last("in_proj"):
            return (f, t)
        if last("out_proj"):
            return (t, f)
        if path.endswith("conv_w"):
            return (None, t)
        if path.endswith(("conv_b",)):
            return (t,)
        if path.endswith(("A_log", "D", "dt_bias")):
            return (None,)
        if "/norm/" in path:
            return (t,)

    # --- rwkv
    if "/tmix/" in path or "/cmix/" in path:
        if re.search(r"/w[rkvg]/w$", path) or last("wk") or last("wr"):
            return (f, t)
        if re.search(r"/(wo|wv)/w$", path):
            return (t, f)
        if path.endswith("maa_w1") or path.endswith("decay_w1"):
            return (f, None)
        if path.endswith("maa_w2"):
            return (None, None, f)
        if path.endswith("decay_w2"):
            return (None, f)
        if path.endswith("u"):
            return (t, None)
        if path.endswith("decay"):
            return (f,)
        if "/ln_x/" in path:
            return (t,)
        return tuple(None for _ in range(ndim))

    # --- embeddings / unembed
    if path.endswith("emb/table"):
        return (t, f)
    if path.endswith("unembed/w"):
        return (f, t)
    if path.endswith("unembed/b"):
        return (t,)
    if path.endswith("pos_dec"):
        return (None, f)

    # norms and anything residual-dim shaped: replicate
    return tuple(None for _ in range(ndim))


def _materialize(spec_tuple, mesh: Mesh):
    fa = fsdp_axes(mesh)
    out = []
    for s in spec_tuple:
        if s == "F":
            out.append(fa if len(fa) > 1 else fa[0])
        else:
            out.append(s)
    return P(*out)


def sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on dims whose size doesn't divide the axis product
    (e.g. odd vocabularies, KV-head counts below the TP degree)."""
    out = []
    for i, s in enumerate(spec):
        if s is None:
            out.append(None)
            continue
        axes = s if isinstance(s, tuple) else (s,)
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        out.append(s if shape[i] % prod == 0 else None)
    return P(*out)


def param_specs(cfg, params_shape, mesh: Mesh, *, moe_sharding: str = "auto"):
    """PartitionSpec pytree matching ``params_shape`` (a pytree of arrays or
    ShapeDtypeStructs)."""
    if moe_sharding == "auto":
        msize = mesh.shape["model"]
        moe_sharding = "ep" if (cfg.num_experts and
                                cfg.num_experts % msize == 0) else "tp"

    def spec(path_elems, leaf):
        path = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path_elems)
        ndim = len(leaf.shape)
        stack = 0
        if _STACKED.search(path):
            stack = 1
            if _EXTRA_STACK.search(path) and "shared_attn" not in path:
                stack = 2
        base = _rule(path, ndim - stack, cfg, moe_sharding)
        full = (None,) * stack + tuple(base)
        if len(full) != ndim:   # fallback: replicate
            full = (None,) * ndim
        return sanitize_spec(_materialize(full, mesh), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def param_shardings(cfg, params_shape, mesh, **kw):
    specs = param_specs(cfg, params_shape, mesh, **kw)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# batch / cache / activation specs
# ---------------------------------------------------------------------------

def batch_spec(mesh: Mesh) -> P:
    return P(fsdp_axes(mesh) if len(fsdp_axes(mesh)) > 1 else fsdp_axes(mesh)[0])


def data_specs(cfg, shape_kind: str, mesh: Mesh, *, batch: int):
    """PartitionSpecs for the input batch dict."""
    dp = fsdp_axes(mesh)
    dp = dp if len(dp) > 1 else dp[0]
    b = P(dp, None)
    specs = {"tokens": b}
    if shape_kind == "train":
        specs["labels"] = b
    if cfg.family == "vlm":
        specs["embeds"] = P(dp, None, None)
    if cfg.family == "encdec":
        specs["frames"] = P(dp, None, None)
    return specs


def cache_specs(cfg, mesh: Mesh, *, batch: int, seq_shard: bool = False):
    """Sharding for decode caches. seq_shard=True -> SP layout for batch=1
    long-context: KV sequence over 'data', heads over 'model'.

    When KV heads don't divide the TP degree (GQA kv=8 on model=16), the
    cache *sequence* shards over 'model' instead — the distributed
    flash-decode LSE merge makes this exact (models/attention.py)."""
    dp = fsdp_axes(mesh)
    dpa = dp if len(dp) > 1 else dp[0]
    seq_ax, batch_ax = (dpa, None) if seq_shard else (None, dpa)
    heads_divisible = cfg.num_kv_heads % mesh.shape["model"] == 0
    if heads_divisible:
        kv = P(None, batch_ax, seq_ax, "model", None)   # (L,B,S,H,D)
    elif seq_shard:
        kv = P(None, batch_ax, (dpa, "model") if not isinstance(dpa, tuple)
               else tuple(dpa) + ("model",), None, None)
    else:
        kv = P(None, batch_ax, "model", None, None)     # seq over model
    scalar = P()
    if cfg.kv_lora_rank:      # one latent for every head: no head axis
        lat = P(None, batch_ax, seq_ax, None)
        names = ("c_kv", "k_pe") + (("dense_c_kv", "dense_k_pe")
                                    if cfg.first_dense_layers else ())
        return dict({n: lat for n in names}, idx=scalar)
    if cfg.family in ("dense", "moe", "vlm"):
        return {"k": kv, "v": kv, "idx": scalar}
    if cfg.family == "ssm":
        return {"layers": {"S": P(None, batch_ax, "model", None, None),
                           "x_att": P(None, batch_ax, None),
                           "x_cmix": P(None, batch_ax, None)},
                "idx": scalar}
    if cfg.family == "hybrid":
        st = {"conv": P(None, batch_ax, None, "model"),
              "ssm": P(None, batch_ax, "model", None, None)}
        st2 = {"conv": P(None, None, batch_ax, None, "model"),
               "ssm": P(None, None, batch_ax, "model", None, None)}
        return {"prologue": st, "groups": st2,
                "attn_k": kv, "attn_v": kv, "idx": scalar}
    if cfg.family == "encdec":
        return {"k": kv, "v": kv, "cross_k": kv, "cross_v": kv, "idx": scalar}
    raise ValueError(cfg.family)
