"""The serve driver through the harness at tiny width on the CPU: a whole
run without the look for a chip, the run's `correct` turned false by a
fault planted in the timed path, the traffic it draws from the seed, and
the command's refusals."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT, tiny_cell

from chipbench import harness
from chipbench.drivers import serve_closed as sc

CELLS = ["qwen2.5-3b.long-prompt", "olmo-1b.long-decode"]
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _execute(cell, seed=7, seconds=10.0):
    # the window's first step prefills every slot eagerly, compiling each
    # prefill's layer scan anew: on a loaded CPU that takes seconds
    return harness.execute(cell, seed=seed, seconds=seconds, trace=False,
                           t_process=time.perf_counter(), device=CPU)


@pytest.mark.parametrize("name", CELLS)
def test_a_run_reports_its_metrics_and_checks(name):
    cell = tiny_cell(name)
    res = _execute(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= cell.traffic["clients"]
    assert res["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    tr = cell.traffic
    assert res["checks"]["sampled_tokens"]["value"] >= \
        tr["check_per_slot"] * tr["slots"] * tr["max_new"]


@pytest.mark.parametrize("name", CELLS)
def test_answers_that_outlast_the_window_are_finished_and_checked(name):
    """A window shorter than one answer: every request sent in it is
    served to its end after the close, and the whole sample is checked."""
    cell = tiny_cell(name, max_new=16, max_seq=32)
    run = sc.run(cell, seed=2**31 + 99, seconds=0.0)
    checks = {c.name: c for c in run.checks}
    assert run.attempted == cell.traffic["clients"] and run.failed == 0
    assert all(c.ok for c in run.checks), checks
    assert checks["sampled_tokens"].value == checks["sampled_tokens"].limit


def _altered_token(make):
    def patched(cfg):
        step = make(cfg)

        def serve_step(params, cache, tokens):
            tok, cache = step(params, cache, tokens)
            return tok.at[0, 0].set((tok[0, 0] + 1) % cfg.vocab_size), cache
        return serve_step
    return patched


def _state_unchanged(make):
    def patched(cfg):
        step = make(cfg)

        def serve_step(params, cache, tokens):
            tok, _ = step(params, cache, tokens)
            return tok, cache
        return serve_step
    return patched


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token-altered", "state-unchanged"])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_decode_step_is_not_correct(name, fault, monkeypatch):
    from repro.serve import decode
    monkeypatch.setattr(decode, "make_serve_step",
                        fault(decode.make_serve_step))
    res = _execute(tiny_cell(name))
    assert res["checks"]["sampled_tokens"]["value"] > 0
    assert not res["correct"], res["checks"]


def test_a_broken_prefill_is_not_correct(monkeypatch):
    """The first token, made by the eager prefill, altered."""
    from repro.models import api
    unembed = api.unembed
    monkeypatch.setattr(api, "unembed", lambda cfg, params, h: jnp.roll(
        unembed(cfg, params, h), 1, axis=-1))
    res = _execute(tiny_cell("qwen2.5-3b.long-prompt"))
    assert not res["correct"], res["checks"]


class _Engine:
    def __init__(self):
        self.queue = []

    def submit(self, req):
        self.queue.append(req)


def _prompts(seed, n=3):
    cell = tiny_cell("qwen2.5-3b.long-prompt")
    loop = sc.Loop(_Engine(), cell.traffic, seed, 256)
    for c in range(n):
        loop.send(c, 0, 0.0)
        loop.send(c, 1, 0.0)
    return [r.prompt for r in loop.eng.queue]


def test_traffic_repeats_for_a_seed_and_differs_for_another():
    big = 2**31 + 12345              # seeds may pass 32 bits
    a, b, c = _prompts(big), _prompts(big), _prompts(big + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not any(np.array_equal(x, y) for x, y in zip(a, c))
    assert len({x.tobytes() for x in a}) == len(a)
    assert all(x.dtype == np.int32 and x.max() < 256 for x in a)


def test_the_sample_is_drawn_from_the_seed_with_the_longest_first():
    """The longest first, then one or two of each slot's requests."""
    class R:
        def __init__(self, n):
            self.out, self.done = [0] * n, True
    loop = sc.Loop(_Engine(), {}, 0, 10)
    lens_slots = [(3, 0), (9, 1), (3, 2), (3, 0), (3, 1), (3, 2), (3, 2)]
    loop.sent = [sc.Sent(R(n), 0, 0, 0.0, slot=k) for n, k in lens_slots]
    picks = [[id(s) for s in sc.sample(loop, seed, 1)] for seed in (1, 1)]
    assert picks[0] == picks[1]
    assert picks[0][0] == id(loop.sent[1])          # the longest, slot 1
    slots = [s.slot for s in sc.sample(loop, 1, 1)]
    assert sorted(slots) == [0, 1, 2]
    two = sc.sample(loop, 1, 2)
    assert sorted(s.slot for s in two) == [0, 0, 1, 1, 2, 2]
    assert len({id(s) for s in two}) == 6
    others = {tuple(id(s) for s in sc.sample(loop, seed, 1))
              for seed in range(8)}
    assert len(others) > 1                          # the seed draws


def test_a_run_records_the_slot_of_every_request(qwen_tiny):
    eng = sc.build(qwen_tiny, 3)
    loop, _, _ = sc.serve(eng, qwen_tiny.traffic, 3, 0.0)
    sc.drain(loop)
    assert {s.slot for s in loop.sent} == set(range(qwen_tiny.traffic["slots"]))


def test_a_metric_with_nothing_to_read_fails_the_run(monkeypatch):
    from chipbench import spec
    cell = tiny_cell(CELLS[0])
    first = cell.end_to_end[0]["name"]
    real = spec.readers

    def none_for_first(metrics, root=ROOT):
        got = real(metrics, root)
        got[first] = lambda run: None
        return got
    monkeypatch.setattr(spec, "readers", none_for_first)
    with pytest.raises(RuntimeError, match=first):
        _execute(cell, seconds=1.0)


def test_the_command_refuses_a_cpu():
    spec = importlib.util.spec_from_file_location(
        "chipbench_run_main", ROOT / "chipbench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    """In a directory with only BENCHMARK.json and the benchmark's paths
    the command fails and prints no result."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    # past the look for a chip, it stops for want of the program
    skip_chip = (
        "import sys, time; sys.path.insert(0, '.');"
        "from chipbench import harness, spec;"
        f"harness.execute(spec.cell({CELLS[0]!r}), seed=1, seconds=1,"
        " trace=False, t_process=time.perf_counter(), device={})")
    proc = subprocess.run([sys.executable, "-c", skip_chip], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
