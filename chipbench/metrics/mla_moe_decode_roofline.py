"""The decode program's share of its roofline for a configuration of latent
attention with routed and shared experts: the least time of the mean
decode step in the window over its mean device time (``decode_step_ms``).

The least time is the larger of FLOPs over peak bf16 FLOP/s and bytes over
HBM bandwidth, per step. FLOPs: the absorbed step, the routed experts at
the pairs the engine's counter says they were given. Bytes: every weight
read once, but the input table, of which the step's rows are read, and the
held experts, of which only those given a token in that step are read;
each slot's filled latent read, and the new one written
(``chipbench/counts_mla_moe.py``). It reads nothing where the run recorded
no routed counts (a driver or program without the counter).
"""
import sys

from chipbench import counts_mla_moe as counts
from chipbench import peaks
from chipbench.counts import least_time_s
from chipbench.readings import decode_step_s


def read(run):
    step_s = decode_step_s(run)
    routed = run.records.get("routed")
    if step_s is None or routed is None:
        return None
    end = run.records["t_end"]
    steps = [(st, r) for st, r in zip(run.records["window_steps"], routed)
             if st.t1 <= end and st.contexts and r is not None]
    if not steps:
        return None
    pk = peaks.peak(run.device_kind)
    conf = run.cell.config
    least, bounds = 0.0, set()
    for st, r in steps:
        t, bound = least_time_s(counts.decode_flops(conf, st.contexts, r),
                                counts.decode_bytes(conf, st.contexts, r),
                                pk)
        least += t
        bounds.add(bound)
    print(f"mla_moe_decode_roofline: {'/'.join(sorted(bounds))} bound",
          file=sys.stderr)
    return 100.0 * (least / len(steps)) / step_s
