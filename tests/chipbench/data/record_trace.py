"""Records a small trace with real device planes: the serve driver at the
tests' tiny width for half a second, traced, and prints what each plane
and line holds. ``test_chipbench_trace.py`` reduces the one committed
beside it (``serve_tiny.xplane.pb.gz``, recorded on a TPU v5e with a
window of 0.05 s).

    python tests/chipbench/data/record_trace.py \
        tests/chipbench/data/serve_tiny.xplane.pb.gz [seconds]

Run it on the chip: a CPU trace has no device planes.
"""
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2]),
                str(HERE.parents[2] / "src")]

from conftest import tiny_cell  # noqa: E402

from chipbench import harness, trace  # noqa: E402
from chipbench.drivers import serve_closed  # noqa: E402


def main(out: str, seconds: float = 0.5) -> None:
    harness.use_compile_cache()
    cell = tiny_cell("qwen2.5-3b.long-prompt", clients=2, slots=2)
    d = tempfile.mkdtemp()
    try:
        serve_closed.run(cell, seed=3, seconds=seconds, trace_dir=d)
        with open(trace.find_xplane(d), "rb") as f, \
                gzip.open(out, "wb", compresslevel=9) as g:
            shutil.copyfileobj(f, g)
        tr = trace.load(trace.find_xplane(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    for plane, lines in tr.devices.items():
        for name, evs in lines.items():
            print(plane, repr(name), len(evs), [e.name for e in evs[:4]])
    for name, evs in tr.host.items():
        print(name, len(evs), sorted({e.name for e in evs})[:12])


if __name__ == "__main__":
    main(sys.argv[1], *(float(a) for a in sys.argv[2:3]))
