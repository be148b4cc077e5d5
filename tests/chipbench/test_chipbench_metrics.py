"""Each metric reader on records made by hand, against numbers worked out
by hand."""
from __future__ import annotations

import pytest
from conftest import ROOT, tiny_cell

from chipbench import counts, harness, peaks, spec
from chipbench import trace as tr
from chipbench.drivers.serve_closed import Sent, Step

KIND = "TPU v5 lite"


class _Req:
    def __init__(self, n):
        self.out = [0] * n


def _run():
    """A window [0, 10): two requests sent at 0 get two tokens at 2 and one
    at 3; a third, sent at 3, gets tokens at 5, 5 and (after the window)
    at 11. Steps: 0-2 admits both, 2-3 decodes, 3-5 admits the third,
    5-6 decodes, 10-11 runs past the window's end."""
    cell = tiny_cell("qwen2.5-3b.long-prompt")
    P = cell.traffic["prompt_len"]
    sent = [Sent(_Req(3), 0, 0, 0.0, [2.0, 2.0, 3.0]),
            Sent(_Req(3), 1, 0, 0.0, [2.0, 2.0, 3.0]),
            Sent(_Req(3), 0, 1, 3.0, [5.0, 5.0, 11.0])]
    steps = [Step(0.0, 2.0, 2, [P + 1, P + 1]), Step(2.0, 3.0, 0, [P + 2] * 2),
             Step(3.0, 5.0, 1, [P + 1]), Step(5.0, 6.0, 0, [P + 2]),
             Step(10.0, 11.0, 0, [P + 2])]
    run = harness.Run(cell=cell, seconds=10.0, setup_s=4.5, attempted=3,
                      failed=0, checks=[], memory_peak_bytes=1,
                      records={"sent": sent, "t_start": 0.0,
                               "t_end": 10.0, "window_steps": steps[:4],
                               "compiles": [1.0, 4.0, 4.5, 7.0]},
                      device_kind=KIND)
    return run, P


def _read(name, run):
    return spec.reader(name, ROOT)(run)


def test_end_to_end_readers():
    run, _ = _run()
    assert _read("setup_s", run) == 4.5
    # TTFT 2, 2, 2: p95 2 s
    assert _read("ttft_p95_ms", run) == pytest.approx(2000.0)
    # 8 tokens came inside the window
    assert _read("output_tokens_per_s", run) == pytest.approx(0.8)
    # gaps inside the window: 0, 1, 0, 1, 0 -> p95 of [0,0,0,1,1] = 1
    assert _read("itl_p95_ms", run) == pytest.approx(1000.0)


def test_engine_readers():
    run, P = _run()
    # admitting steps take 2 + 2 s, decode-only ones 1 s (median):
    # (4 - 2 * 1) s over 3 requests
    assert _read("prefill_wall_ms_per_request", run) == \
        pytest.approx(2000.0 / 3)
    # compiles at 1, 4, 4.5 fall inside counted steps; 7 does not
    assert _read("prefill_compiles_per_request", run) == pytest.approx(1.0)


def test_serve_mfu_counts_the_windows_work():
    run, P = _run()
    conf = run.cell.config
    flops = 3 * counts.prefill_flops(conf, P) + counts.decode_flops(
        conf, [P + 1, P + 1, P + 2, P + 2, P + 1, P + 2])
    want = 100 * flops / (10.0 * peaks.peak(KIND).bf16_flops)
    assert _read("serve_mfu", run) == pytest.approx(want)


def test_trace_readers():
    run, P = _run()
    t = tr.Trace()
    ms = 1e6                            # ns per ms
    t.devices["/device:TPU:0"] = {
        tr.MODULES: [tr.Event("jit_serve_step(7)", 1 * ms, 4 * ms),
                     tr.Event("jit_serve_step(7)", 10 * ms, 6 * ms),
                     tr.Event("jit_scan(9)", 20 * ms, 10 * ms)],
        tr.OPS: [tr.Event("fusion", 1 * ms, 4 * ms),
                 tr.Event("fusion", 10 * ms, 6 * ms),
                 tr.Event("dot", 20 * ms, 10 * ms)]}
    run.trace, run.trace_lo, run.trace_hi = t, 0.0, 40 * ms
    assert _read("decode_step_ms", run) == pytest.approx(5.0)
    assert _read("device_idle_share.serve", run) == pytest.approx(50.0)
    conf, pk = run.cell.config, peaks.peak(KIND)
    least = [counts.least_time_s(counts.decode_flops(conf, c),
                                 counts.decode_bytes(conf, c), pk)[0]
             for c in ([P + 1] * 2, [P + 2] * 2, [P + 1], [P + 2])]
    assert _read("decode_step_roofline", run) == pytest.approx(
        100 * (sum(least) / 4) / 5e-3)
    run.trace = tr.Trace()              # nothing to read: no number
    assert _read("decode_step_ms", run) is None
    assert _read("device_idle_share.serve", run) is None
