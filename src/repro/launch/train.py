"""Production training launcher: mesh construction from real devices,
sharded state init, checkpoint/restart, straggler watchdog with a
SimFA-predicted step deadline, preemption-signal save.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --reduced \
        --dp 1 --tp 1 --batch 8 --seq 64 --steps 20

On a fleet this runs under one process per host (jax.distributed); the
mesh axes here are the single-host equivalent of the production
("pod","data","model") mesh the dry-run validates at 512 chips.
"""
from __future__ import annotations

import argparse
import signal
import time
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt.manager import CheckpointManager
from repro.configs import registry
from repro.configs.llama3 import AttnWorkload
from repro.core.machine import TPU_V5E
from repro.core.tpu.analytical import analyze_tpu
from repro.data.synthetic import DataIterator
from repro.launch.mesh import make_mesh
from repro.parallel import ctx as pctx
from repro.parallel import sharding as shd
from repro.serve.engine import StragglerPolicy
from repro.train import optimizer as opt
from repro.train import trainer
from repro.utils.compile_cache import use_compile_cache


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-trainable)")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="results/train_launch_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10,
                    help="0 turns checkpointing (and restart) off")
    return ap.parse_args(argv)


def init_sharded_state(cfg, run, mesh, key):
    """Train state made on the mesh by one program, each leaf already in
    its sharding: no device ever holds the whole unsharded state.
    Returns (state, param PartitionSpecs, state shardings)."""
    init = partial(trainer.init_state, cfg, run)
    shapes = jax.eval_shape(init, key)
    pspecs = shd.param_specs(cfg, shapes.params, mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    shardings = trainer.TrainState(
        params=pshard,
        opt_state=opt.OptState(m=pshard, v=pshard,
                               step=NamedSharding(mesh, P())),
        ef_error=None if shapes.ef_error is None else pshard)
    state = jax.jit(init, out_shardings=shardings)(key)
    return state, pspecs, shardings


def train(args) -> List[Dict[str, float]]:
    """Runs the launcher's loop; returns each step's metrics as floats."""
    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    n_dev = args.dp * args.tp
    assert n_dev <= jax.device_count(), \
        f"need {n_dev} devices, have {jax.device_count()}"
    mesh = make_mesh((args.dp, args.tp), ("data", "model"),
                     devices=jax.devices()[:n_dev])
    print(f"mesh {dict(mesh.shape)} on {n_dev} device(s); arch {cfg.name} "
          f"({cfg.param_count()/1e6:.1f}M params analytic)")

    run = trainer.RunConfig(
        microbatches=args.microbatches, remat=args.remat,
        opt=opt.OptConfig(lr=args.lr, warmup_steps=10,
                          total_steps=args.steps, schedule=cfg.lr_schedule))

    state, pspecs, shardings = init_sharded_state(
        cfg, run, mesh, jax.random.PRNGKey(0))

    ckpt = (CheckpointManager(args.ckpt_dir, keep_last=2)
            if args.ckpt_every > 0 else None)
    start = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        start, state = ckpt.restore_latest(state, shardings=shardings)
        print(f"[restart] resumed from step {start}")

    # straggler deadline from the paper's performance model: decode/train
    # attention time predicted for the target hardware, scaled by a
    # calibration factor measured on the first step
    w = AttnWorkload(name="train", B=args.batch, L=args.seq, S=args.seq,
                     H_kv=cfg.num_kv_heads or 4, G=cfg.q_group_size or 1,
                     D=cfg.head_dim, causal=True)
    pred = analyze_tpu(w, TPU_V5E)
    watchdog = StragglerPolicy(expected_step_s=1.0, factor=5.0)
    print(f"SimFA-TPU attention prediction for a TPU v5e: "
          f"{pred.latency*1e6:.1f} us/layer ({pred.bottleneck}-bound); "
          f"running on {jax.devices()[0].device_kind} — watchdog "
          f"calibrates off step 1")

    dp = NamedSharding(mesh, shd.batch_spec(mesh))
    step_fn = jax.jit(trainer.make_train_step(cfg, run, grad_specs=pspecs),
                      in_shardings=(shardings, dp),
                      out_shardings=(shardings, None), donate_argnums=0)
    data = DataIterator(cfg, batch=args.batch, seq=args.seq, start_step=start)

    # preemption: SIGTERM triggers a final checkpoint before exit
    preempted = {"flag": False}
    signal.signal(signal.SIGTERM, lambda *a: preempted.__setitem__("flag", True))

    history = []
    with mesh:
        for step in range(start, args.steps):
            batch = {k: jax.device_put(jnp.asarray(v), dp)
                     for k, v in next(data).items()}
            t0 = time.time()
            with pctx.activation_sharding(residual=P("data", None, None)):
                state, metrics = step_fn(state, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
            dt = time.time() - t0
            history.append(metrics)
            if step == start:
                watchdog.expected_step_s = dt      # calibrate
            slow = watchdog.observe(dt)
            print(f"step {step+1:4d} loss={metrics['loss']:.4f} "
                  f"grad_norm={metrics['grad_norm']:.4f} "
                  f"lr={metrics['lr']:.2e} {dt*1e3:.0f}ms"
                  + ("  [STRAGGLER]" if slow else ""), flush=True)
            if ckpt is not None and ((step + 1) % args.ckpt_every == 0
                                     or preempted["flag"]):
                ckpt.save(step + 1, state)
            if preempted["flag"]:
                if ckpt is not None:
                    ckpt.wait()
                print("[preempt] checkpoint published; exiting")
                raise SystemExit(17)
    if ckpt is not None:
        ckpt.wait()
        ckpt.save(args.steps, state, blocking=True)
    print(f"done: {args.steps} steps; {watchdog.slow_steps} straggler "
          f"step(s); checkpoints in "
          f"{args.ckpt_dir if ckpt is not None else '(off)'}")
    return history


def main(argv=None):
    print(f"compile cache: {use_compile_cache()}")
    train(parse_args(argv))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
