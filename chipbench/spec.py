"""Finds a cell's pieces by name from ``BENCHMARK.json`` and the files
beside it, so that a new cell, configuration, traffic mix or metric is
new files and new entries, never an edit:

- ``configs[].file``                  the configuration as it is run
- ``chipbench/traffic/<traffic>.json`` the mix: its driver and parameters
- ``chipbench/limits/<cell>.json``     the limits of the cell's checks
- ``chipbench/drivers/<driver>.py``    ``run(cell, ...)`` for a kind of mix
- ``chipbench/metrics/<metric>.py``    ``read(run)`` for one metric
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
PKG = "chipbench"


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic_name: str
    traffic: dict         # the traffic file's contents
    limits: dict          # check name -> limit
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT, bench: Optional[dict] = None) -> Cell:
    root = Path(root)
    bench = bench or load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    limits_file = root / PKG / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(
            (root / PKG / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=(json.loads(limits_file.read_text())
                if limits_file.exists() else {}),
        end_to_end=[m for m in bench["end_to_end"] if _listed(m, name)],
        per_layer=[m for m in bench["per_layer"] if _listed(m, name)])


def _load_module(path: Path) -> ModuleType:
    """The module at ``path``, loaded once. Files of this checkout get
    their package name (so ``chipbench.drivers.serve_closed`` is one
    module however it is reached); files under another root get a name of
    their own."""
    path = path.resolve()
    if not path.exists():
        raise FileNotFoundError(path)
    try:
        rel = path.relative_to(ROOT).with_suffix("")
        name = ".".join(rel.parts)
    except ValueError:
        name = f"{PKG}_external_{abs(hash(str(path)))}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def driver(name: str, root: Path = ROOT) -> ModuleType:
    return _load_module(Path(root) / PKG / "drivers" / f"{name}.py")


def reader(metric: str, root: Path = ROOT) -> Callable:
    return _load_module(Path(root) / PKG / "metrics" / f"{metric}.py").read


def readers(metrics: List[dict], root: Path = ROOT) -> Dict[str, Callable]:
    return {m["name"]: reader(m["name"], root) for m in metrics}
