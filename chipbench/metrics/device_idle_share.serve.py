"""Share of the traced window in which no operation ran on the device:
1 - busy_s / window_s, the same numbers as the result's ``device``."""
from chipbench import trace as tr


def read(run):
    if run.trace is None:
        return None
    busy = tr.device_busy_s(run.trace, run.trace_lo, run.trace_hi)
    win = (run.trace_hi - run.trace_lo) * 1e-9
    return None if busy is None or win <= 0 else 100.0 * (1 - busy / win)
