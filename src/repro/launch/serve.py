"""Production serving launcher: continuous-batching engine over the same
decode step the dry-run lowers, with SimFA-predicted straggler deadlines.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --requests 8 --slots 4

Without ``--reduced`` the model runs at its published width; weights are
random, drawn from ``--seed``.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.llama3 import AttnWorkload
from repro.core import analytical
from repro.core.machine import H800, TPU_V5E
from repro.core.tpu.analytical import analyze_tpu
from repro.models import api
from repro.serve.engine import Request, ServeEngine, StragglerPolicy
from repro.utils.compile_cache import use_compile_cache


def init_params(cfg, seed: int):
    """Weights in ``cfg.compute_dtype``, made on the device by one program:
    the float32 draw and the cast fuse, so float32 weights never exist."""
    dt = jnp.dtype(cfg.compute_dtype)

    def init(key):
        return jax.tree.map(
            lambda p: p.astype(dt) if jnp.issubdtype(p.dtype, jnp.floating)
            else p, api.init(cfg, key))
    return jax.jit(init)(jax.random.PRNGKey(seed))


@dataclass
class Served:
    cfg: Any
    params: Any
    engine: ServeEngine
    finished: List[Request]
    seconds: float


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=False, help="smoke-scale config (CPU-servable)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    return ap.parse_args(argv)


def serve(args) -> Served:
    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(cfg, args.seed)

    w = AttnWorkload(name="decode", B=args.slots, L=1, S=args.max_seq,
                     H_kv=cfg.num_kv_heads or 4, G=cfg.q_group_size or 1,
                     D=cfg.head_dim)
    pred = analyze_tpu(w, TPU_V5E)
    kind = jax.devices()[0].device_kind
    print(f"SimFA-TPU decode prediction for a TPU v5e: "
          f"{pred.latency*1e6:.1f} us ({pred.bottleneck}-bound); "
          f"running on {kind}")
    # GPU-mode counterpart through the split-KV FlashDecoding kernel's
    # traffic hooks (the serving workload the cycle engine can now see)
    gpu = analytical.analyze(w, H800, kernel="splitkv_decode")
    print(f"SimFA-H800 split-KV decode prediction: {gpu.latency*1e6:.1f} us "
          f"({gpu.bottleneck}-bound, "
          f"{gpu.dram_bytes/1e6:.2f} MB DRAM/step)")

    eng = ServeEngine(cfg, params, slots=args.slots, max_seq=args.max_seq,
                      straggler=StragglerPolicy(expected_step_s=0.5, factor=10))
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size,
                                               args.prompt_len),
                           max_new=args.max_new))
    t0 = time.time()
    finished = eng.run()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in finished)
    print(f"served {len(finished)} requests / {toks} tokens in "
          f"{eng.steps} steps, {dt:.2f}s; "
          f"{eng.straggler.slow_steps} straggler step(s)")
    return Served(cfg, params, eng, finished, dt)


def main(argv=None):
    print(f"compile cache: {use_compile_cache()}")
    serve(parse_args(argv))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
