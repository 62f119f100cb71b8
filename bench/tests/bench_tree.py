"""Helpers for the benchmark's CPU tests: a copy of the benchmark tree at
a size the CPU holds, and one run of a cell in it."""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=4, head_dim=32, intermediate_size=256,
            vocab_size=512)
# The check's limit at this size: sound runs read under 0.01 sigma here,
# the fp8 control over 0.1 (the cells' own limits are set from readings
# at their full sizes on the chip).
TINY_LIMIT = 0.05
# An open-loop cell on the grouped-query configuration (``configs/
# codeqwen1.5-7b.json``), added to every test tree: the open-loop generator
# and the check on a second configuration run as a cell does (the four-chip
# cell it stands for is among PERF.md's open questions).
OPEN_CONFIG = {"name": "codeqwen1.5-7b", "source": "test",
               "file": "bench/configs/codeqwen1.5-7b.json", "reduced": [],
               "why": "test"}
OPEN_CELL = {"name": "codeqwen1.5-7b.tp4.chat", "config": "codeqwen1.5-7b",
             "traffic": "chat", "chips": 4, "why": "test"}


def tiny_tree(dst: pathlib.Path, untied: bool = False) -> pathlib.Path:
    """``dst`` gets ``BENCHMARK.json`` and ``bench/`` with every
    configuration cut to a 2-layer, d_model-128 model and every mix to
    short prompts and answers, with 256-token slots.  ``untied`` unties every configuration's
    output head: at two layers a tied random model mostly repeats its
    input token, whatever its attention does, so no check can fail it."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if OPEN_CELL["name"] not in {w["name"] for w in bench["workloads"]}:
        bench["configs"].append(dict(OPEN_CONFIG))
        bench["workloads"].append(dict(OPEN_CELL))
        (dst / "bench" / "traffic" / "chat.json").write_text(
            json.dumps({"loop": "open"}))
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["config"].update(TINY)
        if untied:
            cfg["config"]["tie_word_embeddings"] = False
        cfg["max_seq_len"] = 256
        cfg["check"]["min_tokens_compared"] = 30
        cfg["check"]["worst_gap_sigma"] = TINY_LIMIT
        (dst / c["file"]).write_text(json.dumps(cfg))
    for w in bench["workloads"]:
        path = dst / "bench" / "traffic" / f"{w['traffic']}.json"
        mix = json.loads(path.read_text())
        mix["prompt_len"] = {"median": 24, "sigma": 0.7, "min": 8, "max": 64}
        # short answers, so that many finish in a window on a busy CPU; the
        # clip stays at 32, above every closed-loop first answer (a part of
        # a full one plus the warm-up tokens), which the reference pads to
        mix["output_len"] = {"median": 6, "sigma": 0.5, "min": 3, "max": 32}
        mix["profile_s"] = 1
        if "max_seq_len" in mix:
            mix["max_seq_len"] = 256
        if "prefill_chunk" in mix:
            mix["prefill_chunk"] = 64
        if mix["loop"] == "closed":
            mix.update(streams=8, warmup_tokens=2)
        else:
            mix.update(rate_per_s=4.0, warmup_s=1)
        path.write_text(json.dumps(mix))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def run_cell(root: pathlib.Path, workload: str, seed: int = 5,
             seconds: float = 8.0, control: int = 0) -> dict:
    """One run of ``workload`` in the tree at ``root``, on the first CPU
    device (the look for a chip is the entry point's, not the run's).  The
    window is long enough for requests to finish on a CPU that other test
    workers share."""
    import jax
    import run as bench_run
    from harness import cells
    cell = cells.load_cell(root, workload)
    cell.chips = 1
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0, control=control)
    return bench_run.run(cell, args, jax.devices()[:1], root=root,
                         out=lambda s: None)
