"""Arithmetic the metric readers share: which records fall in the window,
and percentiles.

A percentile is numpy's default (linear between closest ranks) over every
sample the window gave, never over chunk medians.
"""
from __future__ import annotations

from typing import List

import numpy as np


def p95(xs) -> float | None:
    return float(np.percentile(np.asarray(xs, float), 95)) if len(xs) else None


def window_steps(run) -> List:
    """Steps that ended inside the window."""
    end = run.records["t_end"]
    return [st for st in run.records["window_steps"] if st.t1 <= end]


def token_times(run) -> List[float]:
    """Times at which clients saw a token, inside the window."""
    lo, hi = run.records["t_start"], run.records["t_end"]
    return [t for s in run.records["sent"] for t in s.times if lo <= t <= hi]


def token_gaps(run) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token came inside the window."""
    hi = run.records["t_end"]
    return [b - a for s in run.records["sent"]
            for a, b in zip(s.times, s.times[1:]) if b <= hi]


def decode_step_s(run) -> float | None:
    """Mean device seconds of one execution of the jitted decode program
    (``serve_step``) in the traced window, averaged over the devices."""
    from chipbench import trace as tr
    if run.trace is None:
        return None
    per_dev = [evs for evs in tr.module_events(
        run.trace, r"serve_step", run.trace_lo, run.trace_hi).values() if evs]
    if not per_dev:
        return None
    return sum(sum(e.dur for e in evs) / len(evs)
               for evs in per_dev) / len(per_dev) * 1e-9
