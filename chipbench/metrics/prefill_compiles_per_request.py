"""Programs compiled or loaded from the persistent compile cache during
the window's ``step()`` calls (JAX's ``backend_compile_duration`` events),
per request those calls admitted. The engine's eager prefill builds its
layer scan anew on every call."""
from chipbench.readings import window_steps


def read(run):
    steps = window_steps(run)
    n = sum(st.admitted for st in steps)
    c = sum(1 for t in run.records["compiles"]
            if any(st.t0 <= t <= st.t1 for st in steps))
    return c / n if n else None
