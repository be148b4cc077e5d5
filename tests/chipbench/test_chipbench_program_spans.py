"""The engine layer's metrics that read what the serving engine records of
itself: the three parts of a request's time to a first token, from the
request's timestamps, and the host time of a decode step, from the
engine's ``serve.*`` spans. On records and traces made by hand, on a tiny
traced run on the CPU, and on a trace recorded on a TPU v5e by
``data/record_trace.py``."""
from __future__ import annotations

import gzip
import shutil
import tempfile
from pathlib import Path
from types import SimpleNamespace

import pytest
from conftest import ROOT, tiny_cell

from chipbench import harness, spec
from chipbench import trace as tr
from chipbench.drivers import serve_closed as sc
from chipbench.drivers.serve_closed import Sent
from chipbench.serve_spans import ttft_parts
from chipbench.trace import Event

PARTS = ["queue_wait_ms", "admit_ms_per_request", "first_token_hold_ms"]
READERS = PARTS + ["decode_host_ms_per_step"]
SPANS = {"serve.step", "serve.admit", "serve.prefill", "serve.first_token",
         "serve.splice", "serve.decode", "serve.decode_wait", "serve.fetch"}
MS = 1e6                                # ns per ms


def _read(name, run):
    return spec.reader(name, ROOT)(run)


def _req(t_queued, t_admit, t_first):
    return SimpleNamespace(t_queued=t_queued, t_admit=t_admit,
                           t_first=t_first)


def _run(sent):
    return harness.Run(cell=tiny_cell("qwen2.5-3b.long-prompt"),
                       seconds=10.0, setup_s=1.0, attempted=len(sent),
                       failed=0, checks=[], memory_peak_bytes=1,
                       records={"sent": sent, "t_start": 0.0, "t_end": 10.0,
                                "window_steps": [], "compiles": []})


def test_the_three_parts_add_up_to_each_requests_ttft():
    """Three requests sent at 1.0 into one step: the first admitted at
    1.0 is ready at 1.25, the second admitted at 1.25 ready at 1.5, the
    third admitted at 1.5 ready at 1.75; the step returns at 2.0. A fourth
    has no first token and counts for nothing."""
    sent = [Sent(_req(1.0, 1.0, 1.25), 0, 0, 1.0, [2.0, 2.0]),
            Sent(_req(1.0, 1.25, 1.5), 1, 0, 1.0, [2.0, 2.0]),
            Sent(_req(1.0, 1.5, 1.75), 2, 0, 1.0, [2.0]),
            Sent(_req(1.0, None, None), 3, 0, 1.0, [])]
    run = _run(sent)
    # waits 0, 0.25, 0.5; admissions 0.25 each; held 0.75, 0.5, 0.25
    assert _read("queue_wait_ms", run) == pytest.approx(250.0)
    assert _read("admit_ms_per_request", run) == pytest.approx(250.0)
    assert _read("first_token_hold_ms", run) == pytest.approx(500.0)
    for s, parts in zip(sent, ttft_parts(run)):
        assert sum(parts) == pytest.approx(s.times[0] - s.t_submit)
    total = sum(_read(n, run) for n in PARTS)
    assert total == pytest.approx(1000.0)         # every TTFT is 1 s


def test_requests_without_timestamps_give_nothing():
    """A program whose requests carry no timestamps leaves no number."""
    run = _run([Sent(SimpleNamespace(out=[1]), 0, 0, 0.0, [1.0])])
    assert all(_read(n, run) is None for n in PARTS)


def _trace():
    """One host thread over a window [0, 30) ms: a step that admits
    (skipped), two that only decode, a step that returns before it
    decodes (skipped), and one that runs past the window's end."""
    t = tr.Trace()
    t.host["/host:CPU/python"] = [
        Event("chipbench.window", 0, 30 * MS),
        Event("serve.step", 0, 10 * MS),
        Event("serve.admit", 0, 5 * MS),
        Event("serve.decode", 5 * MS, 1 * MS),
        Event("serve.decode_wait", 6 * MS, 3 * MS),
        Event("serve.step", 10 * MS, 4 * MS),
        Event("serve.decode", 10 * MS, 0.5 * MS),
        Event("serve.decode_wait", 10.5 * MS, 2.5 * MS),
        Event("serve.fetch", 13 * MS, 0.5 * MS),
        Event("serve.step", 14 * MS, 6 * MS),
        Event("serve.decode", 14 * MS, 1 * MS),
        Event("serve.decode_wait", 15 * MS, 4 * MS),
        Event("serve.step", 21 * MS, 1 * MS),
        Event("serve.step", 28 * MS, 4 * MS),
        Event("serve.decode_wait", 28.5 * MS, 3 * MS)]
    # another thread's wait, inside the steps' times, is not a child
    t.host["/host:CPU/worker"] = [Event("serve.decode_wait", 16 * MS,
                                        3 * MS)]
    return t


def test_decode_host_time_skips_admissions_and_subtracts_the_wait():
    run = _run([])
    run.trace, run.trace_lo, run.trace_hi = _trace(), 0.0, 30 * MS
    # (4 - 2.5) ms and (6 - 4) ms
    assert _read("decode_host_ms_per_step", run) == pytest.approx(1.75)
    run.trace = tr.Trace()              # a program without the spans
    assert _read("decode_host_ms_per_step", run) is None
    run.trace = None                    # an untraced run
    assert _read("decode_host_ms_per_step", run) is None


def test_a_traced_cpu_run_records_what_the_readers_read():
    """A tiny closed loop on the CPU, traced: every request's stamps are
    in order, the host plane holds every ``serve.*`` span, and each of the
    four readers gives a number. The three parts add up to the TTFT."""
    cell = tiny_cell("qwen2.5-3b.long-prompt", clients=2, slots=2,
                     max_new=16, max_seq=32)
    d = tempfile.mkdtemp()
    try:
        run = sc.run(cell, seed=2**31 + 5, seconds=3.0, trace_dir=d)
        run.trace = tr.load(tr.find_xplane(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    win = run.trace.host_span("chipbench.window")
    run.trace_lo, run.trace_hi = win.start, win.end
    sent = run.records["sent"]
    assert sent and all(s.times for s in sent)
    for s in sent:
        r = s.req
        assert s.t_submit <= r.t_queued <= r.t_admit <= r.t_first \
            <= s.times[0]
    names = {e.name for evs in run.trace.host.values() for e in evs}
    assert SPANS <= names
    got = {n: _read(n, run) for n in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    ttft = [s.times[0] - s.t_submit for s in sent]
    assert sum(got[n] for n in PARTS) == pytest.approx(
        sum(ttft) / len(ttft) * 1e3, abs=1.0)


def _within(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def test_the_engines_spans_share_the_chips_clock():
    """On a trace recorded on a TPU v5e: every execution of the decode
    program lies between the start of its step's ``serve.decode`` and the
    end of its ``serve.decode_wait``, the spans nest as the engine opens
    them, and the window's idle time is put down to ``serve.*`` spans.
    The host and device clocks of a trace agree to about a millisecond (in
    this one each program starts on the device 0.2 to 1.2 ms before the
    host's call that launched it), so the device's times get 1 ms of
    slack."""
    with tempfile.TemporaryDirectory() as d:
        path = Path(d) / "serve_spans.xplane.pb"
        with gzip.open(Path(__file__).parent / "data"
                       / "serve_spans.xplane.pb.gz") as f, \
                open(path, "wb") as g:
            shutil.copyfileobj(f, g)
        t = tr.load(str(path))
    host = [e for evs in t.host.values() for e in evs]

    def spans(name):
        return [e for e in host if e.name == name]
    steps = spans("serve.step")
    assert steps and SPANS <= {e.name for e in host}
    for name, parent in [("serve.admit", "serve.step"),
                         ("serve.decode", "serve.step"),
                         ("serve.decode_wait", "serve.step"),
                         ("serve.fetch", "serve.step"),
                         ("serve.prefill", "serve.admit"),
                         ("serve.first_token", "serve.admit"),
                         ("serve.splice", "serve.admit")]:
        for e in spans(name):
            assert any(_within(e, p) for p in spans(parent)), (name, e)
    for name in ("serve.decode", "serve.decode_wait", "serve.fetch"):
        assert not any(_within(e, a) for e in spans(name)
                       for a in spans("serve.admit"))

    execs = tr.module_events(t, r"jit_serve_step", -float("inf"),
                             float("inf"))["/device:TPU:0"]
    assert execs
    for e in execs:
        step = next(s for s in steps if s.start <= e.start <= s.end)
        dec, = [x for x in spans("serve.decode") if _within(x, step)]
        wait, = [x for x in spans("serve.decode_wait") if _within(x, step)]
        assert dec.start - 1 * MS <= e.start and e.end <= wait.end + 1 * MS
    win = t.host_span("chipbench.window")
    gaps = tr.idle_gaps(t, win.start, win.end, top=10**6)
    assert any(k.startswith("serve.") for k, _ in gaps)
