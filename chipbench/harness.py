"""One run of one cell: set up, measure, check, reduce, report.

``execute`` drives a cell's driver and turns what it recorded into the
result line. The driver does the work that depends on the kind of mix; the
metric readers (``chipbench/metrics/<name>.py``) turn the records into
numbers. Every reader gets the same :class:`Run`.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List

from chipbench import spec

CACHE_DIR = spec.ROOT / ".chipbench_cache" / "jax"


@dataclass
class Check:
    name: str
    value: float
    limit: float
    ok: bool


@dataclass
class Run:
    """What a driver hands back. Times are ``time.perf_counter()`` seconds;
    ``trace``/``trace_lo``/``trace_hi`` (ns) are set by ``execute`` for a
    traced run."""
    cell: spec.Cell
    seconds: float                  # the measured window's length
    setup_s: float
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    records: Dict[str, Any] = field(default_factory=dict)
    trace: Any = None
    trace_lo: float = 0.0
    trace_hi: float = 0.0
    device_kind: str = ""


def use_compile_cache(path: Path = CACHE_DIR) -> str:
    """JAX's persistent cache at a fixed path inside the checkout: the
    path is part of the cache key, so it never moves. Every program is
    cached, however fast it compiled, so that a second run compiles
    nothing. Nothing is evicted: with a size limit (set by
    ``JAX_COMPILATION_CACHE_MAX_SIZE``, say) JAX's eviction needs a time
    file beside every entry, and one entry written without it makes every
    later write fail."""
    import jax
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


def require_chips(chips: int) -> dict:
    """The device block of the result; exits non-zero off a TPU or with
    fewer chips than the cell needs."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chipbench: needs a TPU, found platform {d.platform!r}")
    if len(devs) < chips:
        sys.exit(f"chipbench: cell needs {chips} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _device_block(run: Run, device: dict) -> dict:
    from chipbench import trace as tr
    out = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    if run.trace is not None:
        busy = tr.device_busy_s(run.trace, run.trace_lo, run.trace_hi)
        out["busy_s"] = busy
        out["window_s"] = (run.trace_hi - run.trace_lo) * 1e-9
    return out


def execute(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
            t_process: float, device: dict,
            root: Path = spec.ROOT) -> dict:
    """Runs the cell and returns the result line's object."""
    driver = spec.driver(cell.traffic["driver"], root)
    metrics_spec = cell.per_layer if trace else cell.end_to_end
    readers = spec.readers(metrics_spec, root)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        run = driver.run(cell, seed=seed, seconds=seconds,
                         trace_dir=trace_dir, t_process=t_process)
        run.device_kind = device.get("kind", "")
        if trace_dir is not None:
            _attach_trace(run, trace_dir)
        metrics = {}
        for m in metrics_spec:
            value = readers[m["name"]](run)
            if value is None:
                # every metric read here is one this cell has to report
                raise RuntimeError(f"{m['name']}: its reader found nothing "
                                   f"to read in {cell.name}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result = {
            "correct": all(c.ok for c in run.checks) and run.failed == 0,
            "attempted": run.attempted, "failed": run.failed,
            "metrics": metrics, "device": _device_block(run, device)}
        if run.trace is not None:
            result["breakdown"] = breakdown(run)
        result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                            for c in run.checks}
        for c in run.checks:
            print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
                  f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
        return result
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _attach_trace(run: Run, trace_dir: str) -> None:
    from chipbench import trace as tr
    run.trace = tr.load(tr.find_xplane(trace_dir))
    win = run.trace.host_span("chipbench.window")
    if win is None:
        raise RuntimeError("the trace has no chipbench.window span")
    run.trace_lo, run.trace_hi = win.start, win.end


def breakdown(run: Run) -> dict:
    from chipbench import trace as tr
    return {"device_ops": tr.op_totals(run.trace, run.trace_lo, run.trace_hi),
            "idle_gaps": tr.idle_gaps(run.trace, run.trace_lo, run.trace_hi)}


def dumps(result: dict) -> str:
    return json.dumps(result, allow_nan=False)

