"""Mean time a request's first token was held after the engine made it
(``Request.t_first``) until its client saw it, when the ``step()`` that
made it returned: the admissions after its own and the step's decode. The
third of the three parts of its time to a first token
(``chipbench/serve_spans.py``)."""
from chipbench.serve_spans import mean_part_ms


def read(run):
    return mean_part_ms(run, 2)
