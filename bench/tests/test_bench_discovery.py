"""A new configuration, traffic mix or per-layer metric is found by its
name: adding one adds files and entries and edits no file that is
there."""
import hashlib
import json
import pathlib

import bench_tree
from harness import cells


def _hashes(root: pathlib.Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_every_entry_resolves():
    bench = cells.load_benchmark(bench_tree.ROOT)
    for w in bench["workloads"]:
        cell = cells.load_cell(bench_tree.ROOT, w["name"])
        assert cell.chips == w["chips"]
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer and all(callable(m.read)
                                      for m in cell.per_layer)


def test_adding_a_cell_mix_and_metric_edits_nothing(tmp_path):
    root = bench_tree.tiny_tree(tmp_path)
    before = _hashes(root)
    cfg = json.loads((root / "bench/configs/minicpm-2b.json").read_text())
    cfg["max_seq_len"] = 128
    (root / "bench/configs/minicpm-2b-short.json").write_text(
        json.dumps(cfg))
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 9.0, "warmup_s": 1,
         "prompt_len": {"median": 16, "sigma": 0.5, "min": 8, "max": 32},
         "output_len": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
         "profile_s": 1}))
    (root / "bench/metrics/rounds_in_window.py").write_text(
        "def read(rec):\n"
        "    return sum(1 for a, _ in rec.steps if rec.in_window(a))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "minicpm-2b-short",
                             "source": "x", "why": "x", "reduced": [],
                             "file": "bench/configs/minicpm-2b-short.json"})
    bench["workloads"].append({"name": "minicpm-2b-short.burst",
                               "config": "minicpm-2b-short",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "rounds_in_window", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "control plane", "moves": "setup_s",
                               "workloads": ["minicpm-2b-short.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell(root, "minicpm-2b-short.burst")
    assert cell.config["max_seq_len"] == 128
    assert cell.traffic["rate_per_s"] == 9.0
    assert "rounds_in_window" in [m.name for m in cell.per_layer]
    old = cells.load_cell(root, "minicpm-2b.streams")
    assert "rounds_in_window" not in [m.name for m in old.per_layer]
    after = _hashes(root)
    assert {k: v for k, v in after.items() if k in before} == before
