"""Readings that a serve cell's ``max_logit_gap`` limit is set from; not
part of a benchmark run.

    python chipbench/calibrate.py --workload olmo-1b.long-decode \
        --seeds 101,102,103 --out calibrate.jsonl

For each seed, in one process: weights from the seed, the engine at the
cell's own load (every client's first request, run to its end), the
run's sample of finished requests, and on it

- ``program``: the widest gap by which a served token's reference logit
  lies below the reference's best (what a run compares with its limit);
- ``control``: the same gap for the token that the reference computed
  with float8 matmuls (``dense_lm.fp8_linear``) puts first: the precision
  step below the configuration's bfloat16.

The limit lies above every program reading and below every control
reading (see PERF.md); each line says what ``correct`` would read with the
program and with the control against the cell's limit.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, spec  # noqa: E402
from chipbench.drivers import serve_closed as sc  # noqa: E402
from chipbench.reference import dense_lm  # noqa: E402


def reading(cell: spec.Cell, seed: int) -> dict:
    t0 = time.perf_counter()
    eng = sc.build(cell, seed)
    loop, _, _ = sc.serve(eng, cell.traffic, seed, 0.0)
    sc.drain(loop)
    t_served = time.perf_counter() - t0
    eng.cache = eng.params = eng.tokens = None
    gc.collect()
    picked = sc.sample(loop, seed, cell.traffic["check_per_slot"])
    gaps, ctl = sc.reference_gaps(cell.config, seed, picked,
                                  cell.traffic["check_block"],
                                  control=dense_lm.fp8_linear)
    limit = cell.limits["max_logit_gap"]
    return {"cell": cell.name, "seed": seed, "program": float(gaps.max()),
            "control": float(ctl.max()), "limit": limit,
            # what a run's `correct` would read with each in the program's
            # place: the program's has to be true, the control's false
            "program_correct": bool(gaps.max() <= limit),
            "control_correct": bool(ctl.max() <= limit),
            "tokens": int(gaps.size),
            "control_median": float(sorted(ctl)[len(ctl) // 2]),
            "served_s": t_served,
            "check_s": time.perf_counter() - t0 - t_served}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, ROOT)
    harness.use_compile_cache()
    harness.require_chips(cell.chips)
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        r = reading(cell, seed)
        line = json.dumps(r)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
