"""Smoke run of the system's main path on a TPU.

    python chip_smoke.py             # one chip: Pallas kernels, then
                                     # qwen2.5-3b served at full width
    python chip_smoke.py --chips 4   # four chips: olmo-1b training at full
                                     # width, a 1x4 mesh against a 2x2 one

Every phase checks its results and raises on failure. Off a TPU the script
exits non-zero before any phase runs; it has no CPU fallback. The last line
of stdout is one JSON object naming the device, printed only when every
phase passed. Weights are random, drawn from a fixed seed.

The phase functions take their sizes as arguments, so the tests run them at
reduced width on the CPU with the kernels in interpret mode.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.configs.llama3 import AttnWorkload  # noqa: E402
from repro.core.tpu.autotune import autotune_flash  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.flash_decode import flash_decode  # noqa: E402
from repro.launch import serve as serve_launcher  # noqa: E402
from repro.launch import train as train_launcher  # noqa: E402
from repro.models import api  # noqa: E402
from repro.models.attention import attention_naive, decode_attend  # noqa: E402
from repro.utils.compile_cache import compile_clock, use_compile_cache  # noqa: E402

# Kernels get bf16 inputs drawn from N(0, 1) and return bf16. Each output
# row is a convex combination of V rows, so |o| < 8 and one bf16 ulp there
# is at most 2**-5; rounding the output alone costs up to half of that
# (0.016). The kernels accumulate in float32, so what they add beyond the
# rounding is far smaller. A wrong mask, scale or head mapping moves
# outputs by O(0.1..1).
KERNEL_ATOL = 2e-2

# The engine's greedy token must be the cache-free forward's argmax unless
# that forward's top-2 logits are closer than this. Logits are bf16 (the
# compute dtype) and with random weights the top ones sit near 4..8, where
# one bf16 ulp is 2**-5. The engine attends at the chip's default precision
# (one bf16 pass for float32 operands) and the reference at "highest"; that
# and the different batch shapes flip some bf16 roundings in each of the 36
# layers. Four ulps at the top logits covers that drift.
LOGIT_GAP_TOL = 4 * 2.0 ** -5

# The 1x4 and 2x2 meshes split the same bf16 matmuls differently, so their
# partial sums are rounded and reduced in different orders (bf16 eps is
# 2**-8 = 0.0039). The loss averages over every token of the batch, which
# shrinks that noise; the gradient norm sums squares over every weight and
# keeps more of it.
LOSS_RTOL = 5e-3
GRAD_NORM_RTOL = 2e-2

SERVE_ARGS = ["--arch", "qwen2.5-3b", "--no-reduced", "--requests", "8",
              "--slots", "4", "--prompt-len", "256", "--max-new", "32",
              "--max-seq", "512", "--seed", "0"]
TRAIN_ARGS = ["--arch", "olmo-1b", "--batch", "8", "--seq", "1024",
              "--steps", "3", "--remat", "full", "--ckpt-every", "0"]


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


def _max_err(out, ref) -> float:
    return float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))


def check_kernels(cfg, *, L=2048, B=4, S=4096, cache_len=3000,
                  interpret=False, seed=0) -> dict:
    """Both Pallas kernels at ``cfg``'s attention widths against the plain
    float32 reference run at highest matmul precision. Returns the max abs
    errors."""
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    errs = {}

    q = _normal(ks[0], (1, H, L, D))
    k = _normal(ks[1], (1, Hkv, L, D))
    v = _normal(ks[2], (1, Hkv, L, D))
    with jax.default_matmul_precision("highest"):
        ref = attention_naive(
            *(x.astype(jnp.float32).transpose(0, 2, 1, 3) for x in (q, k, v)),
            causal=True).transpose(0, 2, 1, 3)
    plan = autotune_flash(AttnWorkload(name="prefill", B=1, L=L, S=L, H_kv=Hkv,
                                       G=H // Hkv, D=D, causal=True))
    for name, blocks in (("default", {}),
                         ("autotuned", {"block_q": plan.block_q,
                                        "block_k": plan.block_k})):
        out = flash_attention(q, k, v, causal=True, interpret=interpret,
                              **blocks)
        errs[f"flash_attention/{name}"] = _max_err(out, ref)

    q = _normal(ks[3], (B, H, D))
    kc = _normal(ks[4], (B, Hkv, S, D))
    vc = _normal(ks[5], (B, Hkv, S, D))
    with jax.default_matmul_precision("highest"):
        ref = decode_attend(
            q[:, None].astype(jnp.float32),
            kc.astype(jnp.float32).transpose(0, 2, 1, 3),
            vc.astype(jnp.float32).transpose(0, 2, 1, 3), cache_len)[:, 0]
    out = flash_decode(q, kc, vc, cache_len, interpret=interpret)
    errs["flash_decode"] = _max_err(out, ref)

    for name, err in errs.items():
        print(f"  {name}: max abs err {err:.3e} (tolerance {KERNEL_ATOL})")
    bad = {n: e for n, e in errs.items() if not e <= KERNEL_ATOL}
    if bad:
        raise AssertionError(f"kernel error above {KERNEL_ATOL}: {bad}")
    return errs


def check_served(served, *, n_check=2) -> dict:
    """Every request got its tokens, and the first ``n_check`` requests'
    greedy tokens match a cache-free forward over prompt + output."""
    eng = served.engine
    reqs = sorted(served.finished, key=lambda r: r.rid)
    want = [r.max_new for r in reqs]
    got = [len(r.out) for r in reqs]
    if not reqs or got != want:
        raise AssertionError(f"tokens per request {got}, expected {want}")

    cfg, params = served.cfg, served.params
    P = eng.prompt_len
    n_new = reqs[0].max_new
    seqs = np.stack([np.concatenate([r.prompt, r.out]) for r in reqs[:n_check]])

    @jax.jit
    def logits_at_outputs(params, tokens):
        hidden, _ = api.forward_hidden(cfg, params, {"tokens": tokens},
                                       remat="none")
        # position P-1+t predicts generated token t
        return api.unembed(cfg, params, hidden[:, P - 1:P - 1 + n_new])

    with jax.default_matmul_precision("highest"):
        logits = logits_at_outputs(params, jnp.asarray(seqs, jnp.int32))
    logits = np.asarray(logits.astype(jnp.float32))
    ref_tok = logits.argmax(-1)
    top2 = np.sort(logits, axis=-1)[..., -2:]
    gap = top2[..., 1] - top2[..., 0]
    out = seqs[:, P:]
    mismatch = ref_tok != out
    bad = mismatch & (gap >= LOGIT_GAP_TOL)
    result = {"positions": int(out.size), "mismatches": int(mismatch.sum()),
              "mismatches_within_gap_tol": int((mismatch & ~bad).sum()),
              "positions_below_gap_tol": int((gap < LOGIT_GAP_TOL).sum()),
              "max_gap_at_mismatch": (float(gap[mismatch].max())
                                      if mismatch.any() else None)}
    print(f"  greedy vs cache-free forward: {result} "
          f"(gap tolerance {LOGIT_GAP_TOL})")
    if bad.any():
        where = [(int(i), int(t), float(gap[i, t])) for i, t in zip(*np.nonzero(bad))]
        raise AssertionError(f"greedy tokens disagree with the forward where "
                             f"its top-2 gap is >= {LOGIT_GAP_TOL}: "
                             f"(request, position, gap) {where[:8]}")
    return result


def check_training(extra_args=(), *, reduced=False) -> dict:
    """The launcher's training loop on a 1x4 (tensor-parallel) mesh, then on
    a 2x2 (data x tensor) mesh; their first steps must agree."""
    base = TRAIN_ARGS + list(extra_args) + (["--reduced"] if reduced else [])
    runs = {}
    for dp, tp in ((1, 4), (2, 2)):
        args = train_launcher.parse_args(base + ["--dp", str(dp),
                                                 "--tp", str(tp)])
        runs[f"{dp}x{tp}"] = train_launcher.train(args)
    a, b = runs["1x4"][0], runs["2x2"][0]
    losses = [m["loss"] for h in runs.values() for m in h]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    rel = {k: abs(a[k] - b[k]) / abs(b[k]) for k in ("loss", "grad_norm")}
    print(f"  step 1: 1x4 loss {a['loss']!r} grad_norm {a['grad_norm']!r}; "
          f"2x2 loss {b['loss']!r} grad_norm {b['grad_norm']!r}; "
          f"relative differences {rel}")
    if not (rel["loss"] <= LOSS_RTOL and rel["grad_norm"] <= GRAD_NORM_RTOL):
        raise AssertionError(
            f"1x4 and 2x2 disagree: {rel} (tolerances loss {LOSS_RTOL}, "
            f"grad_norm {GRAD_NORM_RTOL})")
    return {"step1_rel": rel, "losses": runs}


def _phase(name, fn, *args, **kw):
    print(f"[{name}]", flush=True)
    with compile_clock() as clk:
        out = fn(*args, **kw)
    print(f"[{name}] passed: {clk['compiles']} compiles, "
          f"{clk['compile_s']:.1f} s compiling, {clk['wall_s']:.1f} s wall",
          flush=True)
    return out


def _serve_and_check():
    served = serve_launcher.serve(serve_launcher.parse_args(SERVE_ARGS))
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    check_served(served)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded training comparison")
    args = ap.parse_args(argv)

    dev = device_info()
    print(f"platform {dev['platform']}, device_kind {dev['kind']}, "
          f"{dev['count']} device(s)", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}", flush=True)

    if args.chips == 4:
        _phase("train olmo-1b 1x4 vs 2x2", check_training)
    else:
        _phase("kernels", check_kernels, registry.get("qwen2.5-3b"))
        _phase("serve qwen2.5-3b", _serve_and_check)
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
