"""Mean time a request sent in the window waited in the engine's queue:
from ``submit`` (``Request.t_queued``) to the start of its prefill
(``Request.t_admit``). The first of the three parts of its time to a
first token (``chipbench/serve_spans.py``)."""
from chipbench.serve_spans import mean_part_ms


def read(run):
    return mean_part_ms(run, 0)
