"""What the serving engine records of itself, reduced for the engine
layer's metrics: each request's timestamps (``Request.t_queued``,
``t_admit`` and ``t_first``, on the ``time.perf_counter()`` clock of the
harness's own times) and the engine's ``serve.*`` host spans in the trace.

A request's time to its first token splits into three parts that add up
to it: its wait in the queue (``t_admit - t_queued``), its admission
(prefill, first token and splice: ``t_first - t_admit``) and the time its
first token is held before the ``step()`` that made it returns
(``Sent.times[0] - t_first``). A program that records neither timestamps
nor spans leaves these readers nothing to read.
"""
from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

STAMPS = ("t_queued", "t_admit", "t_first")


def ttft_parts(run) -> List[Tuple[float, float, float]]:
    """(queue wait, admission, first token held), in seconds, of every
    request sent in the window that has a first token and its stamps."""
    parts = []
    for s in run.records["sent"]:
        q, a, f = (getattr(s.req, k, None) for k in STAMPS)
        if s.times and None not in (q, a, f):
            parts.append((a - q, f - a, s.times[0] - f))
    return parts


def mean_part_ms(run, i: int) -> Optional[float]:
    """Mean over the window's requests of part ``i`` of ``ttft_parts``."""
    parts = ttft_parts(run)
    return sum(p[i] for p in parts) / len(parts) * 1e3 if parts else None


def decode_host_s(run) -> List[float]:
    """Per ``serve.step`` span in the traced window that decoded and
    admitted nothing, its host time: the span less its
    ``serve.decode_wait`` child."""
    if run.trace is None:
        return []
    out = []
    for evs in run.trace.host.values():
        starts = [e.start for e in evs]
        for st in evs:
            if st.name != "serve.step" or st.start < run.trace_lo \
                    or st.end > run.trace_hi:
                continue
            inner = [e for e in evs[bisect.bisect_left(starts, st.start):
                                    bisect.bisect_right(starts, st.end)]
                     if e is not st and e.end <= st.end]
            names = {e.name for e in inner}
            if "serve.admit" in names or "serve.decode_wait" not in names:
                continue
            wait = sum(e.dur for e in inner if e.name == "serve.decode_wait")
            out.append((st.dur - wait) * 1e-9)
    return out
