"""Where JAX keeps its persistent compilation cache, and how long compiles
take.

Entry points call :func:`use_compile_cache` from ``main()`` (never at
import). ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
is left alone; otherwise the cache lives at a fixed directory inside the
checkout, so a later run of the same checkout finds it again (the path is
part of the cache key, so it must not move between runs).
"""
from __future__ import annotations

import os
import time
from contextlib import contextmanager
from pathlib import Path

import jax
from jax import monitoring

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def use_compile_cache() -> str:
    """Turn on the persistent cache and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextmanager
def compile_clock():
    """Yields a dict that, once the block ends, holds the number of backend
    compiles in it (``compiles``), their seconds with persistent-cache reads
    included (``compile_s``) and the block's wall seconds (``wall_s``)."""
    out = {"compiles": 0, "compile_s": 0.0, "wall_s": 0.0}

    def listen(event, secs, **_):
        if event == _BACKEND_COMPILE:
            out["compiles"] += 1
            out["compile_s"] += secs

    monitoring.register_event_duration_secs_listener(listen)
    t0 = time.perf_counter()
    try:
        yield out
    finally:
        out["wall_s"] = time.perf_counter() - t0
        monitoring.unregister_event_duration_listener(listen)
