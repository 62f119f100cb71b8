"""The program's own round spans, read in a traced run of a cell at a size
the CPU holds: the four readers of ``harness/spans.py`` read, a round's
host work and device waits add up to its ``step``, the counts the engine
puts on its ``sample`` and ``prefill_chunk`` spans equal what the record
reconstructs, the readers that were there before still read, and so do
the prefill cell's."""
import argparse

import jax
import pytest

import bench_tree
from harness import cells, record

STREAMS, PREFILL = "minicpm-2b.streams", "minicpm-2b.prefill"
NEW = ("host_ms_per_round", "decode_wait_ms", "chunk_wait_ms",
       "supervisor_ms_per_round")
# the device and peak readers need a TPU's planes in the profile
HOST_SIDE = ("control_plane_ms_per_round", "batch_occupancy", "arena_slots")
HOST_SIDE_TTFT = ("chunk_wait_ms.ttft", "decode_wait_ms.ttft",
                  "host_ms_per_round.ttft", "supervisor_ms_per_round.ttft")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the streams cell, with the run's record."""
    import run as bench_run
    tree = bench_tree.tiny_tree(tmp_path_factory.mktemp("bench"))
    cell = cells.load_cell(tree, STREAMS)
    cell.chips = 1
    recs = []
    orig = record.RunRecord.__post_init__

    def keep(self):
        orig(self)
        recs.append(self)

    mp = pytest.MonkeyPatch()
    mp.setattr(record.RunRecord, "__post_init__", keep)
    try:
        args = argparse.Namespace(workload=STREAMS, seed=3_000_000_019,
                                  seconds=8.0, trace=1, control=0)
        res = bench_run.run(cell, args, jax.devices()[:1], root=tree,
                            out=lambda s: None)
    finally:
        mp.undo()
    return cell, res, recs[0]


def test_new_and_old_readers_read(traced):
    cell, res, _ = traced
    got = res["metrics"]
    for name in NEW + HOST_SIDE:
        assert got[name]["value"] is not None, name
    assert {m.name for m in cell.per_layer} >= set(NEW) | set(HOST_SIDE)
    # the supervisor's own span lies inside the harness's span around it,
    # round by round
    assert 0.0 < got["supervisor_ms_per_round"]["value"] <= \
        got["control_plane_ms_per_round"]["value"] + 1e-9


def _ring(rec, tid):
    return sorted((e for e in rec.events if e[1] == rec.svc and e[2] == tid),
                  key=lambda e: e[4])


def test_round_splits_into_host_work_and_waits(traced):
    _, res, rec = traced
    steps = [e for e in _ring(rec, "engine") if e[3] == "step"]
    waits = _ring(rec, "wait")
    assert steps and waits
    names = {w[3] for w in waits}
    assert {"decode", "chunk", "first_token"} <= names
    host = []
    for s in steps:
        inside = [w for w in waits if s[4] <= w[4] and w[5] <= s[5]]
        waited = sum(w[5] - w[4] for w in inside)
        host.append(s[5] - s[4] - waited)
        assert host[-1] >= 0.0
    # every wait lies inside one round
    assert sum(1 for w in waits for s in steps
               if s[4] <= w[4] and w[5] <= s[5]) == len(waits)
    w0, w1 = rec.window
    in_window = [h for s, h in zip(steps, host) if w0 <= s[4] <= w1]
    assert res["metrics"]["host_ms_per_round"]["value"] == pytest.approx(
        1e3 * sum(in_window) / len(in_window))


def test_span_counts_equal_the_reconstruction(traced):
    _, _, rec = traced
    w0, w1 = rec.window
    samples = sorted((e for e in _ring(rec, "engine") if e[3] == "sample"
                      and w0 <= e[5] <= w1), key=lambda e: e[5])
    rebuilt = rec.decode_steps(w0, w1)
    assert samples and len(samples) == len(rebuilt)
    assert [e[6]["keys"] for e in samples] == [sum(k) for k in rebuilt]
    assert [e[6]["live"] for e in samples] == [len(k) for k in rebuilt]
    # chunk starts: the record counts a request's chunks from 0, the engine
    # from where its prefix-cache hit left off
    hit = {int(e[2]): e[6].get("hit_tokens", 0) for e in rec.events
           if e[1] == rec.svc and e[3] == "prefill"}
    ours = sorted((e[6]["start"] - hit[int(e[2])], e[6]["tokens"])
                  for e in rec.events if e[1] == rec.svc
                  and e[3] == "prefill_chunk" and w0 <= e[5] <= w1)
    assert ours and ours == sorted(rec.chunk_calls(w0, w1))



@pytest.fixture(scope="module")
def traced_prefill(tmp_path_factory):
    """One traced run of the prefill cell, with the run's record."""
    import run as bench_run
    tree = bench_tree.tiny_tree(tmp_path_factory.mktemp("bench"))
    cell = cells.load_cell(tree, PREFILL)
    cell.chips = 1
    recs = []
    orig = record.RunRecord.__post_init__

    def keep(self):
        orig(self)
        recs.append(self)

    mp = pytest.MonkeyPatch()
    mp.setattr(record.RunRecord, "__post_init__", keep)
    try:
        args = argparse.Namespace(workload=PREFILL, seed=3_000_000_021,
                                  seconds=6.0, trace=1, control=0)
        res = bench_run.run(cell, args, jax.devices()[:1], root=tree,
                            out=lambda s: None)
    finally:
        mp.undo()
    return tree, cell, res, recs[0]


def test_prefill_cell_readers_read(traced_prefill):
    """The prefill cell's readers of the program's spans and counters
    read; each ``<name>.ttft`` reads what ``<name>`` reads."""
    tree, cell, res, rec = traced_prefill
    got = res["metrics"]
    for name in HOST_SIDE_TTFT:
        assert got[name]["value"] is not None, name
        base = cells.load_reader(tree, name[:-len(".ttft")])
        assert got[name]["value"] == base(rec)
    # prompt tokens per round that ran a chunk call, from the engine's own
    # round spans: no more than a round's chunk budget
    w0, w1 = rec.window
    rounds = [e for e in _ring(rec, "engine") if e[3] == "step"
              and w0 <= e[4] <= w1]
    chunks = [e for e in rec.events if e[1] == rec.svc
              and e[3] == "prefill_chunk"]
    per = [sum(c[6]["tokens"] for c in chunks if r[4] <= c[4] <= r[5])
           for r in rounds]
    per = [n for n in per if n]
    assert per and got["prefill_tokens_per_round"]["value"] == \
        pytest.approx(sum(per) / len(per))
    assert max(per) <= 128
