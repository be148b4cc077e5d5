"""BENCHMARK.json keeps to its contract, and every piece of a cell is found
by name from files alone."""
from __future__ import annotations

import json
import re
import time

import pytest
from conftest import ROOT

from chipbench import harness, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = spec.load_benchmark(ROOT)


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_entries():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [e["name"] for e in metrics + BENCH["workloads"]
             + BENCH["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_is_found_whole(w):
    cell = spec.cell(w["name"], ROOT)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:        # what a layer metric moves is reported
        assert m["moves"] in e2e
    spec.driver(cell.traffic["driver"], ROOT)
    spec.readers(cell.end_to_end + cell.per_layer, ROOT)
    assert "max_logit_gap" in cell.limits
    assert cell.config["name"] == w["config"]


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_cuts(c):
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["source"] == c["source"]
    assert conf["reduced"] == c["reduced"]
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_a_fixture_cell_is_found_from_new_files_alone(tmp_path):
    """A new cell is new files and new entries: nothing of the harness is
    edited. Here a configuration, a mix, a driver and a metric that the
    harness has never seen are found by name and run."""
    pkg = tmp_path / "chipbench"
    for d in ("configs", "traffic", "drivers", "metrics", "limits"):
        (pkg / d).mkdir(parents=True)
    (pkg / "configs" / "toy.json").write_text(json.dumps({"name": "toy"}))
    (pkg / "traffic" / "burst.json").write_text(
        json.dumps({"driver": "toy_driver", "n": 3}))
    (pkg / "limits" / "toy.burst.json").write_text(
        json.dumps({"max_logit_gap": 1.0}))
    (pkg / "drivers" / "toy_driver.py").write_text(
        "from chipbench import harness\n"
        "def run(cell, *, seed, seconds, trace_dir, t_process):\n"
        "    return harness.Run(cell=cell, seconds=seconds, setup_s=0.5,\n"
        "        attempted=cell.traffic['n'], failed=0,\n"
        "        checks=[harness.Check('x', 0.0, 1.0, True)],\n"
        "        memory_peak_bytes=1, records={'seed': seed})\n")
    (pkg / "metrics" / "toy_rate.x.py").write_text(
        "def read(run):\n    return run.records['seed'] * 2\n")
    (pkg / "metrics" / "setup_s.py").write_text(
        "def read(run):\n    return run.setup_s\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "chipbench/configs/toy.json"}],
        "workloads": [{"name": "toy.burst", "config": "toy",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [
            {"name": "toy_rate.x", "unit": "1/s"},
            {"name": "setup_s", "unit": "s"},
            {"name": "other", "unit": "s", "workloads": ["elsewhere"]}],
        "per_layer": []}))
    cell = spec.cell("toy.burst", tmp_path)
    assert cell.traffic["n"] == 3 and cell.limits == {"max_logit_gap": 1.0}
    assert [m["name"] for m in cell.end_to_end] == ["toy_rate.x", "setup_s"]
    res = harness.execute(cell, seed=21, seconds=1.0, trace=False,
                          t_process=time.perf_counter(),
                          device={"platform": "test"}, root=tmp_path)
    assert res["correct"] and res["attempted"] == 3
    assert res["metrics"]["toy_rate.x"] == {"value": 42.0, "unit": "1/s"}
    assert list(res)[-1] == "checks"


def test_unknown_cell_and_missing_reader_are_errors(tmp_path):
    with pytest.raises(KeyError):
        spec.cell("no.such-cell", ROOT)
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric", ROOT)
