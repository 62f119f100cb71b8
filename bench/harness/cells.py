"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A cell (one ``workloads`` entry) names a configuration and a traffic mix.
Its configuration lives in the file its ``configs`` entry names, and that
file names its model family (``"family"``), whose sizes, weight layout,
reference forward and counts live in ``bench/families/<family>.py``; its
mix is ``bench/traffic/<traffic>.json``, and each per-layer metric
``bench/metrics/<metric name>.py`` (or that of the name less its last
``.<suffix>``).  Adding a cell, a mix, a family or a metric adds files
and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType
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
    family: ModuleType                  # bench/families/<family>.py
    traffic_name: str
    traffic: Dict[str, Any]             # the traffic file, as read
    end_to_end: List[Metric]
    per_layer: List[Metric]
    run_seconds: int

    @property
    def max_seq_len(self) -> int:
        """Tokens of one arena slot: the mix's ``max_seq_len`` where it
        sets one (its longest request needs it), else the
        configuration's.  The stack, the compile warm-up and the
        reference's width all read it here."""
        return int(self.traffic.get("max_seq_len",
                                    self.config["max_seq_len"]))

    @property
    def prefill_chunk(self) -> Optional[int]:
        """Prompt tokens prefilled a round: the mix's ``prefill_chunk``
        where it sets one (the plan's knob, the launcher's
        ``--prefill-chunk``), else None, the category's default."""
        chunk = self.traffic.get("prefill_chunk")
        return None if chunk is None else int(chunk)


def load_benchmark(root: pathlib.Path) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load(path: pathlib.Path, what: str, prefix: str) -> ModuleType:
    """The module at ``path``, loaded by path (names hold dots) once, and
    registered under its name so that its dataclasses resolve."""
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"{what}: no {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def load_reader(root: pathlib.Path, name: str) -> Callable:
    """The per-layer metric ``name``'s reader: ``read(rec)`` in
    ``bench/metrics/<name>.py``.  Where there is no such file and the name
    is ``<base>.<suffix>``, the reader of ``<base>``: one quantity split by
    name where cells report different end-to-end metrics reads alike."""
    metrics = root / BENCH_DIR / "metrics"
    base = name
    while not (metrics / f"{base}.py").is_file() and "." in base:
        base = base.rpartition(".")[0]
    if not (metrics / f"{base}.py").is_file():
        base = name
    return _load(metrics / f"{base}.py", f"per-layer metric {name!r}",
                 "bench_metric_").read


def load_family(root: pathlib.Path, name: str) -> ModuleType:
    """The model family ``name``: ``bench/families/<name>.py`` (see
    ``bench/families/dense.py`` for what one provides)."""
    return _load(root / BENCH_DIR / "families" / f"{name}.py",
                 f"model family {name!r}", "bench_family_")


def _applies(entry: Dict[str, Any], cell: str, reported=None) -> bool:
    """Whether a metric belongs to ``cell``: its ``workloads`` name it, or
    it has none and (for a per-layer metric) the cell reports the
    end-to-end metric it ``moves``."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return reported is None or entry["moves"] in reported


def load_cell(root: pathlib.Path, name: str) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    path = root / configs[w["config"]]["file"]
    with open(path) as f:
        config = json.load(f)
    if "family" not in config:
        raise KeyError(f"configuration {path} names no \"family\"")
    with open(root / BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m.name for m in e2e}
    per = [Metric(m["name"], m["unit"], load_reader(root, m["name"]))
           for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                family=load_family(root, config["family"]),
                traffic_name=w["traffic"], traffic=traffic, end_to_end=e2e,
                per_layer=per, run_seconds=int(bench["run_seconds"]))
