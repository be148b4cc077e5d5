"""The serving engine's host spans and request timestamps: tracing changes
no token, and an admission's span names its request and slot."""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import registry
from repro.launch.serve import init_params
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def model():
    cfg = registry.get("qwen2.5-3b").reduced()
    return cfg, init_params(cfg, 0)


def _serve(model):
    """Three requests on two slots: the third waits for a free slot."""
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=2, max_seq=16)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8),
                    max_new=4) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """The same requests served untraced, then traced; and the stats of
    every ``serve.admit`` span of the trace."""
    plain = _serve(model)
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        traced = _serve(model)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    admits = [dict(e.stats) for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events
              if e.name == "serve.admit"]
    return plain, traced, admits


def test_tracing_changes_no_token(served):
    plain, traced, _ = served
    assert [r.out for r in traced] == [r.out for r in plain]
    for r in plain + traced:
        assert r.done and r.t_queued <= r.t_admit <= r.t_first
    # the third request waited for the first to finish
    assert plain[2].t_admit > plain[0].t_first


def test_each_admission_names_its_request_and_slot(served):
    *_, admits = served
    assert sorted((a["rid"], a["slot"]) for a in admits) == \
        [(0, 0), (1, 1), (2, 0)]
