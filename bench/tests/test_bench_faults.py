"""The check that decides ``correct`` catches a broken timed path.

Each case drives a whole run of a cell (the look for a chip skipped, at a
size the CPU holds) with one fault planted under the serving path, and
sees ``correct`` come out false; the unbroken runs come out true.  Output
heads are untied here: at two layers a tied random model mostly repeats
its input token, whatever its attention does."""
import jax.numpy as jnp
import pytest

import bench_tree
from harness import cells

STREAMS, CHAT = "minicpm-2b.streams", "codeqwen1.5-7b.tp4.chat"
PREFILL = "minicpm-2b.prefill"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_tree.tiny_tree(tmp_path_factory.mktemp("bench"),
                                untied=True)


def _token_altered(monkeypatch):
    """A token altered where it is produced: the sampler's first row."""
    from repro.serving import engine
    orig = engine.sample_per_slot

    def altered(logits, *a, **k):
        out = orig(logits, *a, **k)
        return out.at[0].set((out[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(engine, "sample_per_slot", altered)


def _half_batch(monkeypatch):
    """Half of the decode batch left out of attention."""
    from repro.kernels import ops
    orig = ops.paged_decode_attention

    def half(q, *a, **k):
        out = orig(q, *a, **k)
        # the slots in use are the first ones: leave out every other one
        keep = jnp.arange(out.shape[0]) % 2 == 0
        return jnp.where(keep[:, None, None], out, 0)

    monkeypatch.setattr(ops, "paged_decode_attention", half)


def _state_unchanged(monkeypatch):
    """Steps that return the cache unchanged: no K/V row is written."""
    from repro.models import layers
    monkeypatch.setattr(layers, "paged_insert_rows",
                        lambda pages, *a, **k: pages)


def _exchange_left_out(monkeypatch):
    """The exchange between chips left out: each head shard's attention
    output stays on its chip, so only the first quarter of the heads
    reaches the output projection (emulated on one device)."""
    from repro.kernels import ops

    def local(name):
        orig = getattr(ops, name)

        def only_first_shard(q, *a, **k):
            out = orig(q, *a, **k)
            h = out.shape[-2]
            keep = jnp.arange(h) < max(1, h // 4)
            return jnp.where(keep[:, None], out, 0)

        monkeypatch.setattr(ops, name, only_first_shard)

    local("paged_decode_attention")
    local("paged_chunk_attention")


@pytest.mark.parametrize("workload", [STREAMS, CHAT, PREFILL])
def test_sound_run_is_correct(tree, workload):
    res = bench_tree.run_cell(tree, workload)
    assert res["correct"], res["checks"]
    assert res["checks"]["worst_gap_sigma"]["value"] <= \
        res["checks"]["worst_gap_sigma"]["limit"]
    cell = cells.load_cell(tree, workload)
    assert {m.name for m in cell.end_to_end} == set(res["metrics"])


@pytest.mark.parametrize("workload,fault", [
    (CHAT, _token_altered), (CHAT, _half_batch),
    (CHAT, _state_unchanged), (CHAT, _exchange_left_out),
    (STREAMS, _token_altered), (STREAMS, _half_batch),
    (STREAMS, _state_unchanged), (PREFILL, _token_altered),
    (PREFILL, _half_batch), (PREFILL, _state_unchanged)],
    ids=["token_altered", "half_batch", "state_unchanged",
         "exchange_left_out", "streams-token_altered", "streams-half_batch",
         "streams-state_unchanged", "prefill-token_altered",
         "prefill-half_batch", "prefill-state_unchanged"])
def test_fault_makes_run_incorrect(tree, workload, fault, monkeypatch):
    fault(monkeypatch)
    res = bench_tree.run_cell(tree, workload)
    assert not res["correct"], res["checks"]
