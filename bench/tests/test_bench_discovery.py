"""A new configuration, traffic mix, model family or per-layer metric is
found by its name: adding one adds files and entries and edits no file
that is there."""
import hashlib
import json
import pathlib

import numpy as np
import pytest

import bench_tree
from harness import cells, reference, weights


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


def test_per_layer_metrics_move_what_their_cells_report():
    """A per-layer metric belongs to the cells its ``workloads`` list, or,
    without one, to every cell that reports the end-to-end metric it
    ``moves``; either way each of its cells reports that metric."""
    bench = cells.load_benchmark(bench_tree.ROOT)
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    for w in bench["workloads"]:
        cell = cells.load_cell(bench_tree.ROOT, w["name"])
        reported = {m.name for m in cell.end_to_end}
        assert all(moves[m.name] in reported for m in cell.per_layer)
    prefill = cells.load_cell(bench_tree.ROOT, "minicpm-2b.prefill")
    streams = cells.load_cell(bench_tree.ROOT, "minicpm-2b.streams")
    assert "mfu" in {m.name for m in streams.per_layer}
    assert "mfu" not in {m.name for m in prefill.per_layer}


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


def test_a_split_metric_reads_its_base_unless_it_has_a_file(tmp_path):
    """``<base>.<suffix>`` with no file of its own is read by ``<base>``'s
    reader, a suffix at a time; a file of its own wins; a name with
    neither is an error that names the file it lacks."""
    root = bench_tree.tiny_tree(tmp_path)
    metrics = root / "bench" / "metrics"
    assert cells.load_reader(root, "chunk_wait_ms.ttft") is \
        cells.load_reader(root, "chunk_wait_ms")
    assert cells.load_reader(root, "step_mfu.decode.ttft") is \
        cells.load_reader(root, "step_mfu.decode")
    assert cells.load_reader(root, "step_mfu.decode.ttft") is not \
        cells.load_reader(root, "step_mfu.prefill")
    (metrics / "chunk_wait_ms.split.py").write_text(
        "def read(rec):\n    return 7.0\n")
    assert cells.load_reader(root, "chunk_wait_ms.split")(None) == 7.0
    missing = metrics / "no_such_metric.ttft.py"
    with pytest.raises(FileNotFoundError, match=str(missing)):
        cells.load_reader(root, "no_such_metric.ttft")


# A family that is not the dense one: GPT-J-style parallel blocks, where
# attention and the MLP read the same normed input and both add to the
# residual stream.  Only the family file and a configuration naming it are
# new; the harness, the traffic and the metrics are the ones there.
PARALLEL = """
import dataclasses

import jax
import jax.numpy as jnp

from harness.counts import chunk_keys
from harness.reference import HI, _mm, _rms, _rope


def program_config(config):
    return {}


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    d_ff: int
    vocab: int
    kv_dtype: str
    eps: float = 1e-5
    theta: float = 1e4

    @property
    def head_dim(self):
        return self.d_model // self.heads


def dims(config, kv_dtype):
    c = config["config"]
    return Dims(c["num_hidden_layers"], c["hidden_size"],
                c["num_attention_heads"], c["intermediate_size"],
                c["vocab_size"], kv_dtype)


def layout(d):
    L, m, f = d.layers, d.d_model, d.d_ff
    return {"embed": {"embedding": ((d.vocab, m), "embed")},
            "blocks": {"ln": {"w": ((L, m), "norm")},
                       "wqkv": ((L, m, 3 * m), "matrix"),
                       "wo": ((L, m, m), "matrix"),
                       "w_in": ((L, m, f), "matrix"),
                       "w_out": ((L, f, m), "matrix")},
            "ln_f": {"w": ((m,), "norm")}}


def logits(params, d, tokens, *, low=False):
    L, H, D = tokens.shape[0], d.heads, d.head_dim
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        h = _rms(x, lp["ln"]["w"], d.eps)
        q, k, v = jnp.split(_mm(h, lp["wqkv"], low), 3, -1)
        q = _rope(q.reshape(L, H, D), d.theta)
        k = _rope(k.reshape(L, H, D), d.theta)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * D ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v.reshape(L, H, D),
                       precision=HI).reshape(L, H * D)
        mlp = _mm(jax.nn.gelu(_mm(h, lp["w_in"], low)), lp["w_out"], low)
        return x + _mm(o, lp["wo"], low) + mlp, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x, params["ln_f"]["w"].astype(jnp.float32), d.eps)
    head = params["embed"]["embedding"].astype(jnp.float32).T
    return _mm(x, head, low)


def _token_ops(d):
    return 2 * (d.layers * (4 * d.d_model ** 2 + 2 * d.d_model * d.d_ff)
                + d.d_model * d.vocab)


def _kv(d):
    return 2 * d.d_model * (1 if d.kv_dtype == "int8" else 2)


def decode_step(d, lens):
    lens = list(lens)
    return (float(len(lens) * _token_ops(d)
                  + 4 * d.layers * d.d_model * sum(lens)),
            float(d.layers * _kv(d) * sum(lens)))


def paged_decode_kernel(d, lens):
    lens = list(lens)
    return (float(4 * d.layers * d.d_model * sum(lens)),
            float(d.layers * _kv(d) * sum(lens)))


def chunk_step(d, start, n):
    return (float(n * _token_ops(d)
                  + 4 * d.layers * d.d_model * chunk_keys(start, n)),
            float(d.layers * _kv(d) * (start + n)))


def paged_chunk_kernel(d, start, n):
    return (float(4 * d.layers * d.d_model * chunk_keys(start, n)),
            float(d.layers * _kv(d) * (start + n)))
"""


def test_adding_a_family_edits_nothing(tmp_path):
    root = bench_tree.tiny_tree(tmp_path)
    before = _hashes(root)
    (root / "bench/families/parallel.py").write_text(PARALLEL)
    cfg = json.loads((root / "bench/configs/minicpm-2b.json").read_text())
    cfg["family"] = "parallel"
    (root / "bench/configs/tiny-parallel.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-parallel", "source": "x",
                             "why": "x", "reduced": [],
                             "file": "bench/configs/tiny-parallel.json"})
    bench["workloads"].append({"name": "tiny-parallel.streams",
                               "config": "tiny-parallel",
                               "traffic": "streams", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell(root, "tiny-parallel.streams")
    fam = cell.family
    assert fam.__file__ == str(root / "bench/families/parallel.py")
    dims = fam.dims(cell.config, "int8")
    params = weights.init_weights(fam.layout(dims), 2 ** 31 + 5)
    assert params["blocks"]["wqkv"].shape == (2, 128, 384)
    # the reference runs through the harness's check: the tokens it puts
    # first are served with no gap, the control's are judged
    prompt = np.arange(1, 17, dtype=np.int32)
    best = np.asarray(fam.logits(params, dims, prompt)).argmax(-1)
    served = np.array([best[-1]], np.int32)
    gaps = reference.served_gaps(params, fam.logits, dims, [(prompt, served)],
                                 width=cell.max_seq_len, served_max=4)
    assert gaps[0].shape == (1,) and gaps[0][0] == 0.0
    ctl = reference.served_gaps(params, fam.logits, dims, [(prompt, served)],
                                width=cell.max_seq_len, served_max=4,
                                control=True)
    assert np.isfinite(ctl[0]).all()
    # the counting metrics read the family's counts
    for ops, byt in (fam.decode_step(dims, [10, 20]),
                     fam.paged_decode_kernel(dims, [10, 20]),
                     fam.chunk_step(dims, 32, 64),
                     fam.paged_chunk_kernel(dims, 32, 64)):
        assert ops > 0 and byt > 0
    after = _hashes(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        pathlib.Path("bench/families/parallel.py"),
        pathlib.Path("bench/configs/tiny-parallel.json")}
