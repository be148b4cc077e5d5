"""Host wall time the engine spends admitting one request (eager prefill,
first token, splice): the wall time of the window's ``step()`` calls that
admitted requests, less the median wall time of those that only decoded
once per such call, over the requests admitted."""
import statistics

from chipbench.readings import window_steps


def read(run):
    steps = window_steps(run)
    admit = [st for st in steps if st.admitted]
    decode = [st.t1 - st.t0 for st in steps if not st.admitted
              and st.contexts]
    n = sum(st.admitted for st in admit)
    if not n or not decode:
        return None
    extra = sum(st.t1 - st.t0 for st in admit) \
        - len(admit) * statistics.median(decode)
    return extra / n * 1e3
