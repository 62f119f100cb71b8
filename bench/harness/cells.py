"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A cell (one ``workloads`` entry) names a configuration and a traffic mix.
Its configuration lives in the file its ``configs`` entry names, its mix
in ``bench/traffic/<traffic>.json``, and each per-layer metric in
``bench/metrics/<metric name>.py``.  Adding a cell, a mix or a metric adds
files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = "bench"


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Optional[Callable] = None     # per-layer metrics: reader(rec)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]              # the configuration file, as read
    traffic_name: str
    traffic: Dict[str, Any]             # the traffic file, as read
    end_to_end: List[Metric]
    per_layer: List[Metric]
    run_seconds: int


def load_benchmark(root: pathlib.Path) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_reader(root: pathlib.Path, name: str) -> Callable:
    """The per-layer metric ``name``'s reader: ``read(rec)`` in
    ``bench/metrics/<name>.py``, loaded by path (names hold dots)."""
    path = root / BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"per-layer metric {name!r}: no {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, name)]
    per = [Metric(m["name"], m["unit"], load_reader(root, m["name"]))
           for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic_name=w["traffic"], traffic=traffic, end_to_end=e2e,
                per_layer=per, run_seconds=int(bench["run_seconds"]))
