"""Mean host time of an engine step that only decoded: over the traced
window's ``serve.step`` spans that hold no ``serve.admit``, the span less
its ``serve.decode_wait`` child: the slot loop, the dispatch and the
token fetch, the host's work between one decode step's wait and the
next."""
from chipbench.serve_spans import decode_host_s


def read(run):
    xs = decode_host_s(run)
    return sum(xs) / len(xs) * 1e3 if xs else None
