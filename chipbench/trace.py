"""Reduces a profiler trace (``*.xplane.pb``) to plain events, and events to
the numbers the metrics read: busy time, program durations, idle gaps.

Device planes are named ``/device:TPU:<n>``. On each, the ``XLA Modules``
line holds one event per program execution (``jit_serve_step(...)``) and
the ``XLA Ops`` line one per HLO operation. Host planes (``/host:...``)
hold the threads' spans: the harness's own ``TraceAnnotation`` names and
JAX's dispatch spans. All start times share one clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

MODULES = "XLA Modules"
OPS = "XLA Ops"


@dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns
    dur: float        # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    # plane name -> line name -> events sorted by start
    devices: Dict[str, Dict[str, List[Event]]] = field(default_factory=dict)
    host: Dict[str, List[Event]] = field(default_factory=dict)

    def device_lines(self, line: str) -> Dict[str, List[Event]]:
        return {p: lines[line] for p, lines in self.devices.items()
                if line in lines}

    def host_span(self, name: str) -> Optional[Event]:
        """The longest host event called ``name``."""
        found = [e for evs in self.host.values() for e in evs
                 if e.name == name]
        return max(found, key=lambda e: e.dur) if found else None


def start(log_dir: str) -> None:
    """Starts the profiler without its Python tracer, which would record
    every Python call, slow the host it measures and fill the trace; the
    host keeps its ``TraceAnnotation`` spans and the runtime's own."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") and \
            "CPU" not in plane.name
        is_host = plane.name.startswith("/host:CPU")
        if not (is_dev or is_host):
            continue
        for line in plane.lines:
            evs = sorted((Event(e.name, float(e.start_ns),
                                float(e.duration_ns))
                          for e in line.events), key=lambda e: e.start)
            if is_dev:
                tr.devices.setdefault(plane.name, {})[line.name] = evs
            else:
                tr.host[f"{plane.name}/{line.name}"] = evs
    return tr


def clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by any event."""
    spans: List[Tuple[float, float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if spans and e.start <= spans[-1][1]:
            if e.end > spans[-1][1]:
                spans[-1] = (spans[-1][0], e.end)
        else:
            spans.append((e.start, e.end))
    return spans


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    return sum(t - s for s, t in union(clip(events, lo, hi)))


def device_busy_s(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """Seconds in which an operation ran, averaged over the devices."""
    per_dev = [busy_ns(evs, lo, hi)
               for evs in trace.device_lines(OPS).values()]
    return sum(per_dev) / len(per_dev) * 1e-9 if per_dev else None


def module_events(trace: Trace, pattern: str, lo: float,
                  hi: float) -> Dict[str, List[Event]]:
    """Per device, the executions of programs whose name matches."""
    rx = re.compile(pattern)
    return {p: [e for e in evs if rx.search(e.name)
                and e.start >= lo and e.end <= hi]
            for p, evs in trace.device_lines(MODULES).items()}


def op_totals(trace: Trace, lo: float, hi: float,
              top: int = 10) -> List[list]:
    """[program:op, seconds] over all devices, largest first. Each op is
    named by the program execution it falls in, and by its HLO name
    without the instruction's text."""
    tot: Dict[str, float] = defaultdict(float)
    for plane, lines in trace.devices.items():
        mods = clip(lines.get(MODULES, []), lo, hi)
        j = 0
        for e in clip(lines.get(OPS, []), lo, hi):
            while j < len(mods) and mods[j].end <= e.start:
                j += 1
            mod = mods[j].name if j < len(mods) and \
                mods[j].start <= e.start else "?"
            tot[f"{_short(mod)}:{e.name.split(' = ')[0]}"] += e.dur * 1e-9
    return [[k, v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def _short(name: str) -> str:
    return name.split("(")[0]


def idle_gaps(trace: Trace, lo: float, hi: float,
              top: int = 10) -> List[list]:
    """Idle device time in [lo, hi), summed by what the host was doing:
    the latest-starting host span that covers each gap's midpoint, the
    shorter of two that start together (spans nest, so that is the
    innermost)."""
    host = sorted((e for evs in trace.host.values() for e in evs
                   if e.dur > 0), key=lambda e: (e.start, -e.dur))
    starts = [e.start for e in host]
    tot: Dict[str, float] = defaultdict(float)
    for evs in trace.device_lines(OPS).values():
        prev = lo
        for s, t in union(clip(evs, lo, hi)) + [(hi, hi)]:
            if s > prev:
                mid = (prev + s) / 2
                name = "(no host span)"
                i = bisect.bisect_right(starts, mid) - 1
                for e in host[max(i - 4096, 0):i + 1][::-1]:
                    if e.end >= mid:
                        name = e.name
                        break
                tot[name] += (s - prev) * 1e-9
            prev = max(prev, t)
    n = max(len(trace.device_lines(OPS)), 1)
    return [[k, v / n] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]
