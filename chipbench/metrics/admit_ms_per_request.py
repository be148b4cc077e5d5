"""Mean time the engine took to admit a request sent in the window: from
the start of its prefill (``Request.t_admit``) to its first token
(``Request.t_first``), so prefill, the wait for the first token and the
splice into its slot. The second of the three parts of its time to a
first token (``chipbench/serve_spans.py``)."""
from chipbench.serve_spans import mean_part_ms


def read(run):
    return mean_part_ms(run, 1)
