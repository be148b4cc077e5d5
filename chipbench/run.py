"""Runs one cell of the benchmark on the chip this process finds.

    python chipbench/run.py --workload qwen2.5-3b.long-prompt --seed 7 \
        --seconds 30 --trace 0

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same checks are the last lines of stderr. Off a TPU, or with fewer
chips than the cell needs, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, spec  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.cell(args.workload, ROOT)
    device = harness.require_chips(cell.chips)
    harness.use_compile_cache()
    result = harness.execute(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_process=T_PROCESS,
                             device=device, root=ROOT)
    sys.stdout.flush()
    print(harness.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
