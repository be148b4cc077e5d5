"""The decode program's share of its roofline: the least time of the mean
decode step in the window over its mean device time (``decode_step_ms``).

The least time is the larger of FLOPs over peak bf16 FLOP/s and bytes
over HBM bandwidth. FLOPs: every slot's new token through the matmuls,
attention over the positions that slot has filled, the unembed. Bytes:
every weight read once, each slot's filled K/V read, the new K/V written
(``chipbench/counts.py``). That is the same work whatever implements it.
"""
import sys

from chipbench import counts, peaks
from chipbench.readings import decode_step_s, window_steps


def read(run):
    step_s = decode_step_s(run)
    steps = [st for st in window_steps(run) if st.contexts]
    if step_s is None or not steps:
        return None
    pk = peaks.peak(run.device_kind)
    least, bounds = 0.0, set()
    for st in steps:
        t, bound = counts.least_time_s(
            counts.decode_flops(run.cell.config, st.contexts),
            counts.decode_bytes(run.cell.config, st.contexts), pk)
        least += t
        bounds.add(bound)
    print(f"decode_step_roofline: {'/'.join(sorted(bounds))} bound",
          file=sys.stderr)
    return 100.0 * (least / len(steps)) / step_s
