"""Output tokens that reached a client inside the window, per second of
the window."""
from chipbench.readings import token_times


def read(run):
    n = len(token_times(run))
    return n / run.seconds if n else None
