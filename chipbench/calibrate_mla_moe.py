"""``calibrate.py``'s readings for a cell whose driver is
``serve_closed_mla_moe`` (latent attention with routed and shared
experts): per seed, each check of ``serve_closed_mla_moe`` read on the
program's tokens and on the float8 control's, against
``reference/mla_moe_lm.py``; not part of a benchmark run.

    python chipbench/calibrate_mla_moe.py \
        --workload moonlight-16b-a3b.long-decode-32 --seeds 101,102,103
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, spec  # noqa: E402
from chipbench.drivers import serve_closed as sc  # noqa: E402
from chipbench.drivers import serve_closed_mla_moe as smm  # noqa: E402
from chipbench.reference import mla_moe_lm  # noqa: E402


def reading(cell: spec.Cell, seed: int) -> dict:
    t0 = time.perf_counter()
    eng = smm.build(cell, seed)
    loop, _, _ = sc.serve(eng, cell.traffic, seed, 0.0)
    sc.drain(loop)
    t_served = time.perf_counter() - t0
    eng.cache = eng.params = eng.tokens = None
    gc.collect()
    picked = sc.sample(loop, seed, cell.traffic["check_per_slot"])
    gaps, ctl = smm.reference_gaps(cell.config, seed, picked,
                                   cell.traffic["check_block"],
                                   control=mla_moe_lm.fp8_linear)
    out = {"cell": cell.name, "seed": seed, "tokens": int(gaps.size)}
    for who, g in (("program", gaps), ("control", ctl)):
        read = smm.gap_readings(g)
        out[who] = read
        # what a run's `correct` would read with each in the program's
        # place: the program's has to be true, the control's false
        out[f"{who}_correct"] = all(v <= cell.limits[k]
                                    for k, v in read.items())
        out[f"{who}_median"] = float(np.median(g))
        out[f"{who}_share_above_0"] = float((g > 0).mean())
    out["limits"] = cell.limits
    out["served_s"] = t_served
    out["check_s"] = time.perf_counter() - t0 - t_served
    return out


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
        line = json.dumps(reading(cell, seed))
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
