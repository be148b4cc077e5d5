"""95th percentile, over every request sent in the window, of the time
from its submission to the return of the ``step()`` that gave its first
token."""
from chipbench.readings import p95


def read(run):
    v = p95([s.times[0] - s.t_submit for s in run.records["sent"]
             if s.times])
    return None if v is None else v * 1e3
