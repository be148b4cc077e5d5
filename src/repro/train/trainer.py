"""Train step factory: microbatched gradient accumulation, remat, MoE aux
loss, gradient compression hook, and sharding-aware jit wiring.

``make_train_step(cfg, run)`` returns a function
    train_step(state, batch) -> (state, metrics)
suitable for ``jax.jit(..., in_shardings=..., donate_argnums=0)``. The
gradient-accumulation scan defers the cross-replica gradient reduction to
the single optimizer application (one psum per step instead of one per
microbatch — the standard comm/compute overlap trick).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import api
from repro.train import optimizer as opt
from repro.train.loss import chunked_cross_entropy


@dataclass(frozen=True)
class RunConfig:
    microbatches: int = 1
    remat: str = "full"
    moe_aux_weight: float = 0.01
    loss_chunk: int = 2048
    grad_dtype: str = "float32"        # gradient accumulator dtype
    grad_compress: str = "none"        # none | int8 (error-feedback)
    opt: opt.OptConfig = opt.OptConfig()


class TrainState(NamedTuple):
    params: dict
    opt_state: opt.OptState
    ef_error: Optional[dict]           # int8 compression error feedback


def init_state(cfg, run: RunConfig, key):
    params = api.init(cfg, key)
    ef = (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
          if run.grad_compress == "int8" else None)
    return TrainState(params=params, opt_state=opt.init(params), ef_error=ef)


def _quantize_int8(g, err):
    """Error-feedback int8 compression: models a compressed gradient
    all-reduce (the quantize->sum->dequantize pipeline); the quantization
    residual is fed back into the next step."""
    gf = g.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127)
    deq = q * scale
    return deq, gf - deq


def make_loss_fn(cfg, run: RunConfig):
    def loss_fn(params, mb):
        hidden, aux = api.forward_hidden(cfg, params, mb, remat=run.remat)
        s_tok = mb["labels"].shape[1]
        loss, w = chunked_cross_entropy(
            hidden[:, -s_tok:], api.unembed_table(cfg, params), mb["labels"],
            chunk=run.loss_chunk)
        total = loss + run.moe_aux_weight * jnp.asarray(aux, jnp.float32)
        return total, {"loss": loss, "aux": jnp.asarray(aux, jnp.float32)}
    return loss_fn


def make_train_step(cfg, run: RunConfig, grad_specs=None):
    """grad_specs: optional PartitionSpec pytree matching the params.
    Constraining the gradient accumulator to the parameter sharding turns
    the per-microbatch gradient reduction into a reduce-scatter onto the
    FSDP shards instead of a full all-reduce of replicated gradients
    (half the wire bytes, and no replicated accumulator in HBM)."""
    loss_fn = make_loss_fn(cfg, run)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def _constrain(tree):
        if grad_specs is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree, grad_specs)

    def train_step(state: TrainState, batch):
        params = state.params
        n_mb = run.microbatches

        if n_mb == 1:
            (_, metrics), grads = grad_fn(params, batch)
            grads = _constrain(grads)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape((n_mb, b // n_mb) + x.shape[1:])
            mbs = jax.tree.map(split, batch)

            gdt = jnp.dtype(run.grad_dtype)

            def body(acc, mb):
                (_, m), g = grad_fn(params, mb)
                acc = jax.tree.map(lambda a, b: a + b.astype(gdt), acc, g)
                return _constrain(acc), m
            zero = _constrain(jax.tree.map(
                lambda p: jnp.zeros(p.shape, gdt), params))
            grads, ms = jax.lax.scan(body, zero, mbs)
            grads = jax.tree.map(lambda g: g / n_mb, grads)
            metrics = jax.tree.map(lambda m: m.mean(), ms)

        ef = state.ef_error
        if run.grad_compress == "int8":
            pairs = jax.tree.map(_quantize_int8, grads, ef)
            grads = jax.tree.map(lambda t: t[0], pairs,
                                 is_leaf=lambda x: isinstance(x, tuple))
            ef = jax.tree.map(lambda t: t[1], pairs,
                              is_leaf=lambda x: isinstance(x, tuple))

        new_params, new_opt, om = opt.apply_updates(
            run.opt, params, grads, state.opt_state)
        metrics = dict(metrics, **om)
        return TrainState(new_params, new_opt, ef), metrics

    return train_step
