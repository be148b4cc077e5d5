"""Per-cell planning: input ShapeDtypeStructs, shardings, and step functions
for every (arch x shape x mesh) dry-run cell.

``plan_cell`` applies the memory napkin math (16 GiB/chip budget) to choose
microbatch count, optimizer-state dtype, and activation sequence-sharding
from the configuration, the shape and the mesh.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import api
from repro.parallel import ctx as pctx
from repro.parallel import sharding as shd
from repro.serve import decode as serve_decode
from repro.train import optimizer as opt
from repro.train import trainer

HBM_BUDGET = 14.5e9          # leave headroom under 16 GiB/chip


@dataclass(frozen=True)
class CellPlan:
    microbatches: int = 1
    remat: str = "full"
    moe_sharding: str = "auto"
    opt_dtype: str = "float32"
    grad_dtype: str = "float32"
    seq_shard_acts: bool = False
    seq_shard_cache: bool = False      # SP for long-context decode
    loss_chunk: int = 2048


def plan_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> CellPlan:
    n_dev = mesh.size
    fsdp = 1
    for a in shd.fsdp_axes(mesh):
        fsdp *= mesh.shape[a]
    tp = mesh.shape["model"]
    plan = CellPlan()
    if shape.kind == "train":
        n_params = cfg.param_count()
        # params fp32 + grads fp32 + adam m/v
        opt_dtype = "float32"
        per_param = 4 + 4 + 8
        if n_params * per_param / n_dev > 0.7 * HBM_BUDGET:
            opt_dtype = "bfloat16"
            per_param = 4 + 4 + 4
        fixed = n_params * per_param / n_dev
        # sequence-parallel residual stream by default: scan-saved remat
        # carries shard over ('model') too (measured 77GB -> 5.6GB on olmo)
        seq_shard = True
        # ssm/hybrid token-mixers need the whole sequence per layer (token
        # shift / conv / scan): intra-layer activations only shard over DP,
        # and run several fp32 passes -> much larger per-token constant
        if cfg.family in ("ssm", "hybrid"):
            tokens_local = shape.tokens / fsdp
            act_per_tok = cfg.d_model * 4 * 10
        else:
            tokens_local = shape.tokens / fsdp / tp
            act_per_tok = cfg.d_model * 2 * (cfg.num_layers + 8) * 3
        budget = max(HBM_BUDGET - fixed, 1e9)
        mb = 1
        while mb < 64 and tokens_local / mb * act_per_tok > budget:
            mb *= 2
        mb = min(mb, int(max(1, shape.global_batch // fsdp)))
        grad_dtype = "float32"
        if n_params * (per_param + 8) / n_dev > HBM_BUDGET:
            grad_dtype = "bfloat16"   # accumulate grads in bf16 (giant MoE)
        plan = dataclasses.replace(
            plan, microbatches=mb, opt_dtype=opt_dtype, grad_dtype=grad_dtype,
            seq_shard_acts=seq_shard)
    elif shape.kind == "decode" and shape.global_batch < fsdp:
        # batch can't fill the data axis -> shard the KV sequence instead
        plan = dataclasses.replace(plan, seq_shard_cache=True)
    return plan


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


def batch_struct(cfg: ModelConfig, shape: ShapeConfig, *, kind: str):
    B, S = shape.global_batch, shape.seq_len
    s_tok = S
    out = {}
    if cfg.family == "vlm":
        s_tok = S - cfg.frontend_len
        out["embeds"] = _sds((B, cfg.frontend_len, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        out["frames"] = _sds((B, cfg.frontend_len, cfg.d_model), jnp.bfloat16)
    out["tokens"] = _sds((B, s_tok), jnp.int32)
    if kind == "train":
        out["labels"] = _sds((B, s_tok), jnp.int32)
    return out


def _to_struct(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _cast_float(tree, dtype):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype),
        tree)


def _with_activation_ctx(fn, plan: CellPlan, mesh: Mesh, cfg=None):
    dp = shd.fsdp_axes(mesh)
    dpa = dp if len(dp) > 1 else dp[0]
    residual = P(dpa, "model" if plan.seq_shard_acts else None, None)
    residual_dec = P(None if plan.seq_shard_cache else dpa, None, None)
    kinds = dict(residual=residual, residual_dec=residual_dec)
    if cfg is not None and cfg.num_kv_heads:
        heads_div = cfg.num_kv_heads % mesh.shape["model"] == 0
        if heads_div:
            # collected prefill KV: (B, S, Hkv, D) heads over model
            kinds["kv_collect"] = P(dpa, None, "model", None)
        else:
            # seq over model; decode scores stay sharded on S -> psum stats
            kinds["kv_collect"] = P(dpa, "model", None, None)
            kinds["scores_dec"] = P(
                None if plan.seq_shard_cache else dpa, None, None, None, "model")

    def wrapped(*args):
        with pctx.activation_sharding(**kinds):
            return fn(*args)

    return wrapped


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """Returns dict(fn, args (ShapeDtypeStructs), in_shardings, donate,
    plan, meta) ready for jit().lower()."""
    plan = plan_cell(cfg, shape, mesh)
    key = jax.random.PRNGKey(0)

    param_struct = jax.eval_shape(lambda: api.init(cfg, key))
    pspecs = shd.param_specs(cfg, param_struct, mesh,
                             moe_sharding=plan.moe_sharding)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    dspec = shd.data_specs(cfg, shape.kind, mesh, batch=shape.global_batch)
    bstruct = batch_struct(cfg, shape, kind=shape.kind)
    bshard = {k: NamedSharding(
        mesh, shd.sanitize_spec(dspec[k], bstruct[k].shape, mesh))
        for k in bstruct}

    if shape.kind == "train":
        run = trainer.RunConfig(
            microbatches=plan.microbatches, remat=plan.remat,
            loss_chunk=plan.loss_chunk, grad_dtype=plan.grad_dtype,
            opt=opt.OptConfig())
        step = _with_activation_ctx(trainer.make_train_step(cfg, run),
                                    plan, mesh, cfg)
        opt_dt = jnp.dtype(plan.opt_dtype)
        m_struct = _cast_float(param_struct, opt_dt)
        state_struct = trainer.TrainState(
            params=param_struct,
            opt_state=opt.OptState(m=m_struct, v=m_struct,
                                   step=_sds((), jnp.int32)),
            ef_error=None)
        state_shard = trainer.TrainState(
            params=pshard,
            opt_state=opt.OptState(m=pshard, v=pshard,
                                   step=NamedSharding(mesh, P())),
            ef_error=None)
        return dict(
            fn=step, args=(state_struct, bstruct),
            in_shardings=(state_shard, bshard),
            donate=(0,), plan=plan,
            meta=dict(kind="train", tokens=shape.tokens))

    serve_params = _cast_float(param_struct, jnp.bfloat16)

    if shape.kind == "prefill":
        step = _with_activation_ctx(
            serve_decode.make_prefill_step(cfg, max_seq=shape.seq_len),
            plan, mesh, cfg)
        return dict(
            fn=step, args=(serve_params, bstruct),
            in_shardings=(pshard, bshard),
            donate=(), plan=plan,
            meta=dict(kind="prefill", tokens=shape.tokens))

    # decode: one new token against a full cache
    B, S = shape.global_batch, shape.seq_len
    cache_struct = jax.eval_shape(
        lambda: api.init_cache(cfg, B, S, dtype=jnp.bfloat16))
    cspec = shd.cache_specs(cfg, mesh, batch=B, seq_shard=plan.seq_shard_cache)
    cspec = jax.tree.map(
        lambda s, st: shd.sanitize_spec(s, st.shape, mesh),
        cspec, cache_struct, is_leaf=lambda x: isinstance(x, P))
    cshard = jax.tree.map(lambda s: NamedSharding(mesh, s), cspec,
                          is_leaf=lambda x: isinstance(x, P))
    tok_struct = _sds((B, 1), jnp.int32)
    tok_shard = NamedSharding(
        mesh, P(None) if plan.seq_shard_cache else shd.batch_spec(mesh))
    step = _with_activation_ctx(serve_decode.make_serve_step(cfg), plan, mesh, cfg)
    return dict(
        fn=step, args=(serve_params, cache_struct, tok_struct),
        in_shardings=(pshard, cshard, tok_shard),
        donate=(1,), plan=plan,
        meta=dict(kind="decode", tokens=B))
