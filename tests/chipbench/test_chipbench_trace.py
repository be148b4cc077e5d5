"""The trace reduction on events made here, and on a small trace of a
closed-loop serve run recorded on a TPU v5e by ``data/record_trace.py``."""
from __future__ import annotations

import gzip
import shutil
from pathlib import Path

import pytest

from chipbench import trace as tr
from chipbench.trace import Event


def _trace():
    """One device: a program of two ops, idle, another program. The host
    was in `engine.step` throughout, and inside it `prefill` during the
    second gap."""
    t = tr.Trace()
    t.devices["/device:TPU:0"] = {
        tr.MODULES: [Event("jit_serve_step(1)", 10, 30),
                     Event("jit_prefill(2)", 70, 20)],
        tr.OPS: [Event("fusion.1", 10, 20), Event("fusion.2", 25, 15),
                 Event("dot.3", 70, 20)]}
    t.host["/host:CPU/python"] = [Event("engine.step", 0, 100),
                                  Event("prefill", 45, 20),
                                  Event("chipbench.window", -1, 102)]
    return t


def test_union_busy_and_clip():
    t = _trace()
    ops = t.devices["/device:TPU:0"][tr.OPS]
    assert tr.union(ops) == [(10, 40), (70, 90)]
    assert tr.busy_ns(ops, 0, 100) == 50
    assert tr.busy_ns(ops, 30, 80) == 20
    assert tr.device_busy_s(t, 0, 100) == pytest.approx(50e-9)


def test_programs_ops_and_idle_gaps():
    t = _trace()
    mods = tr.module_events(t, r"serve_step", 0, 100)
    assert [e.name for e in mods["/device:TPU:0"]] == ["jit_serve_step(1)"]
    ops = tr.op_totals(t, 0, 100)
    assert [k for k, _ in ops] == ["jit_serve_step:fusion.1",
                                   "jit_prefill:dot.3",
                                   "jit_serve_step:fusion.2"]
    assert [v for _, v in ops] == pytest.approx([20e-9, 20e-9, 15e-9])
    gaps = dict((k, v) for k, v in tr.idle_gaps(t, 0, 100))
    # idle: [0,10) and [90,100) under engine.step; [40,70) midpoint 55
    # inside the prefill span
    assert gaps == {"engine.step": pytest.approx(20e-9),
                    "prefill": pytest.approx(30e-9)}
    assert t.host_span("chipbench.window").dur == 102


def test_a_trace_recorded_on_the_chip(tmp_path):
    """The reduction finds the chip's planes, lines and programs: the
    window's span, the decode program, op names without the instruction's
    text, and busy plus idle time that fill the window."""
    path = tmp_path / "serve_tiny.xplane.pb"
    with gzip.open(Path(__file__).parent / "data"
                   / "serve_tiny.xplane.pb.gz") as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    t = tr.load(str(path))
    assert list(t.devices) == ["/device:TPU:0"]
    assert {tr.MODULES, tr.OPS} <= set(t.devices["/device:TPU:0"])
    win = t.host_span("chipbench.window")
    lo, hi = win.start, win.end
    assert t.host_span("engine.step").dur <= win.dur
    steps = tr.module_events(t, r"serve_step", lo, hi)["/device:TPU:0"]
    assert steps and all(e.name.startswith("jit_serve_step(") for e in steps)
    busy = tr.device_busy_s(t, lo, hi)
    assert 0 < busy < win.dur * 1e-9
    ops = tr.op_totals(t, lo, hi)
    assert len(ops) == 10 and all(" = " not in k for k, _ in ops)
    assert any(k.startswith("jit_serve_step:%") for k, _ in ops)
    gaps = tr.idle_gaps(t, lo, hi, top=10**6)
    assert sum(v for _, v in gaps) + busy == pytest.approx(win.dur * 1e-9)
