"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
        [--mesh single|multi|both] [--out results/dryrun.json]

``main()`` sets ``XLA_FLAGS`` to 512 host devices before its first device
query, so it runs in a process of its own. Results are written
incrementally (resumable; --force recomputes)."""

import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax

from repro.configs import registry
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib
from repro.utils.hlo import collective_bytes, hlo_cost, xla_cost_analysis

OUT_DEFAULT = "results/dryrun.json"


def run_cell(cfg, shape, mesh, mesh_kind: str) -> dict:
    t0 = time.time()
    cell = specs_lib.build_cell(cfg, shape, mesh)
    with mesh:
        jitted = jax.jit(
            cell["fn"],
            in_shardings=cell["in_shardings"],
            donate_argnums=cell["donate"] or None)
        lowered = jitted.lower(*cell["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = xla_cost_analysis(compiled)
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)
    # XLA's cost_analysis counts while-loop (scan) bodies ONCE; hlo_cost
    # multiplies by trip counts — use it for the roofline terms and keep
    # the raw XLA numbers for reference (utils/hlo.py docstring).
    hc = hlo_cost(hlo_text)
    plan = cell["plan"]
    n_dev = mesh.size
    rec = {
        "arch": cfg.name, "shape": shape.name, "mesh": mesh_kind,
        "status": "ok",
        "devices": n_dev,
        "kind": cell["meta"]["kind"],
        "tokens": cell["meta"]["tokens"],
        "plan": {k: getattr(plan, k) for k in
                 ("microbatches", "remat", "moe_sharding",
                  "opt_dtype", "grad_dtype", "seq_shard_acts",
                  "seq_shard_cache")},
        "flops_per_device": hc["flops"],
        "bytes_per_device": hc["bytes"],
        "xla_flops_per_device": cost.get("flops", 0.0),
        "xla_bytes_per_device": cost.get("bytes accessed", 0.0),
        "collective_bytes_per_device": coll.get("total", 0.0),
        "collectives": {k: v for k, v in coll.items() if not k.startswith("count")},
        "collective_counts": {k: v for k, v in coll.items() if k.startswith("count")},
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes + mem.temp_size_in_bytes),
        },
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
    }
    return rec


def main():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=OUT_DEFAULT)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--include-extra", action="store_true",
                    help="also run llama3-8b (not part of the 40 cells)")
    args = ap.parse_args()

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", mesh_lib.make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       mesh_lib.make_production_mesh(multi_pod=True)))

    cells = list(registry.cells(include_extra=args.include_extra))
    for cfg, shape, supported, why in cells:
        if args.arch and cfg.name != args.arch:
            continue
        if args.shape and shape.name != args.shape:
            continue
        for mesh_kind, mesh in meshes:
            key = f"{cfg.name}|{shape.name}|{mesh_kind}"
            if key in results and results[key].get("status") == "ok" \
                    and not args.force:
                print(f"[skip cached] {key}")
                continue
            if not supported:
                results[key] = {"arch": cfg.name, "shape": shape.name,
                                "mesh": mesh_kind, "status": "skipped",
                                "reason": why}
                print(f"[skip arch] {key}: {why}")
                out_path.write_text(json.dumps(results, indent=1))
                continue
            print(f"[lower+compile] {key} ...", flush=True)
            try:
                rec = run_cell(cfg, shape, mesh, mesh_kind)
                peak = rec["mem"]["peak_bytes"]
                print(f"  ok: flops/dev={rec['flops_per_device']:.3g} "
                      f"peak={peak/1e9:.2f}GB coll={rec['collective_bytes_per_device']:.3g}B "
                      f"compile={rec['t_compile_s']}s", flush=True)
            except Exception as e:  # noqa: BLE001 - record and continue
                rec = {"arch": cfg.name, "shape": shape.name,
                       "mesh": mesh_kind, "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                print(f"  ERROR {type(e).__name__}: {str(e)[:300]}", flush=True)
            results[key] = rec
            out_path.write_text(json.dumps(results, indent=1))
    n_ok = sum(1 for r in results.values() if r.get("status") == "ok")
    n_err = sum(1 for r in results.values() if r.get("status") == "error")
    n_skip = sum(1 for r in results.values() if r.get("status") == "skipped")
    print(f"done: {n_ok} ok, {n_err} errors, {n_skip} skipped -> {out_path}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
