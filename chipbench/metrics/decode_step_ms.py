"""Mean device time of one execution of the jitted decode program
(``serve_step``), from the trace, averaged over the devices."""
from chipbench.readings import decode_step_s


def read(run):
    s = decode_step_s(run)
    return None if s is None else s * 1e3
