"""95th percentile of every gap between consecutive tokens of one request
as its client saw them. A request's first two tokens come out of one
``step()``, so their gap of 0 counts."""
from chipbench.readings import p95, token_gaps


def read(run):
    v = p95(token_gaps(run))
    return None if v is None else v * 1e3
