"""The traffic generator: deterministic by seed, one schedule for every
seed, and the lengths and gaps follow the mix's distributions."""
import statistics

import numpy as np
import pytest

import bench_tree  # puts bench/ and src/ on the path
from harness import traffic

STREAMS = {"loop": "closed", "streams": 8, "first_prompt_len": 128,
           "warmup_tokens": 10,
           "prompt_len": {"median": 128, "sigma": 0.7, "min": 32,
                          "max": 512},
           "output_len": {"median": 160, "sigma": 0.7, "min": 32,
                          "max": 512}}
CHAT = {"loop": "open", "rate_per_s": 3.0, "warmup_s": 5,
        "prompt_len": {"median": 640, "sigma": 0.6, "min": 128,
                       "max": 2048},
        "output_len": {"median": 96, "sigma": 0.6, "min": 16, "max": 256}}
BIG_SEED = 2 ** 31 + 12345


def _closed(n=40):
    loop = traffic.ClosedLoop(STREAMS)
    return [loop.next(s % 8) for s in range(n)]


def test_same_seed_same_traffic():
    a, b = _closed(), _closed()
    assert [(r.idx, r.prompt_len, r.max_new) for r in a] == \
        [(r.idx, r.prompt_len, r.max_new) for r in b]
    assert np.array_equal(traffic.prompt_tokens(BIG_SEED, 3, 50, 1000),
                          traffic.prompt_tokens(BIG_SEED, 3, 50, 1000))
    o1 = traffic.OpenLoop(CHAT, 30).phases["window"]
    o2 = traffic.OpenLoop(CHAT, 30).phases["window"]
    assert [(r.arrival_s, r.prompt_len) for r in o1] == \
        [(r.arrival_s, r.prompt_len) for r in o2]


def test_seeds_change_the_tokens_not_the_schedule():
    # the schedule takes no seed; the prompt's token ids do
    assert not np.array_equal(traffic.prompt_tokens(1, 0, 50, 1000),
                              traffic.prompt_tokens(2, 0, 50, 1000))
    a = traffic.prompt_tokens(BIG_SEED, 0, 2000, 1000)
    assert a.min() >= 0 and a.max() < 1000 and len(set(a.tolist())) > 800


@pytest.mark.parametrize("spec", [STREAMS["prompt_len"],
                                  CHAT["prompt_len"],
                                  CHAT["output_len"]])
def test_lengths_follow_the_lognormal(spec):
    x = traffic.quantile_lengths(spec, 2000)
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    assert abs(np.median(x) - spec["median"]) <= 1
    # log-space spread of the unclipped middle: sigma
    lo, hi = np.percentile(np.log(x), [25, 75])
    assert abs((hi - lo) / 1.349 - spec["sigma"]) < 0.02


def test_open_loop_count_and_span():
    loop = traffic.OpenLoop(CHAT, 40)
    win = loop.phases["window"]
    assert len(win) == round(3.0 * 40)
    assert len(loop.phases["warmup"]) == round(3.0 * 5)
    at = [r.arrival_s for r in win]
    assert at[0] == 0.0 and all(b > a for a, b in zip(at, at[1:]))
    assert at[-1] < 40
    gaps = np.diff(at)
    # exponential gaps: mean 1/rate, coefficient of variation near 1
    assert abs(gaps.mean() - 1 / 3.0) < 0.02
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    assert len({r.idx for r in loop.phases["warmup"] + win}) == \
        len(win) + len(loop.phases["warmup"])


def test_closed_loop_first_requests_are_under_way():
    loop = traffic.ClosedLoop(STREAMS)
    first = [loop.next(s) for s in range(8)]
    later = [loop.next(s) for s in range(8)]
    # residual lives: evenly spaced fractions of full lengths, plus the
    # tokens decoded during the warm-up, fewer the later a stream's first
    # prompt is prefilled
    assert statistics.mean(r.max_new for r in first) < \
        statistics.mean(r.max_new for r in later)
    assert all(r.prompt_len == 128 for r in first)
    full = np.random.default_rng(0).permutation(
        traffic.quantile_lengths(STREAMS["output_len"], 8))
    left = np.ceil((np.arange(8) + 0.5) / 8 * full)
    ahead = [r.max_new - left[i] for i, r in enumerate(first)]
    assert ahead == [10, 9, 8, 7, 5, 4, 3, 2]
    assert max(r.max_new for r in first) <= STREAMS["output_len"]["max"]
    assert len({r.idx for r in first + later}) == 16
    # the j-th requests of all streams take the pool's evenly spaced lengths
    assert sorted(r.max_new for r in later) == sorted(
        traffic.quantile_lengths(STREAMS["output_len"], 8).tolist())


def _prefill():
    import json
    return json.loads((bench_tree.ROOT / "bench" / "traffic"
                       / "prefill.json").read_text())


def test_prefill_mix_schedule_is_fixed_and_clipped():
    mix = _prefill()
    a = traffic.OpenLoop(mix, 45)
    b = traffic.OpenLoop(mix, 45)
    for tag in ("warmup", "window"):
        assert [(r.arrival_s, r.prompt_len, r.max_new)
                for r in a.phases[tag]] == \
            [(r.arrival_s, r.prompt_len, r.max_new) for r in b.phases[tag]]
    reqs = a.phases["warmup"] + a.phases["window"]
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(p["min"] <= r.prompt_len <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new <= o["max"] for r in reqs)
    assert len(a.phases["window"]) == round(mix["rate_per_s"] * 45)
    # the longest request fits the mix's slot
    assert p["max"] + o["max"] <= mix["max_seq_len"]


@pytest.mark.parametrize("workload,slot,chunk",
                         [("minicpm-2b.prefill", 2048, 512),
                          ("minicpm-2b.streams", 1024, None)])
def test_slot_length_reaches_the_stack(workload, slot, chunk, monkeypatch):
    """The mix's ``max_seq_len`` overrides the configuration's, and its
    ``prefill_chunk`` the category's chunk; the stack is built with both
    (the compile warm-up and the reference's width read the same
    ``Cell.max_seq_len``)."""
    import argparse
    import jax
    import run as bench_run
    from harness import cells, stack

    class Built(Exception):
        pass

    def build(config, family, seed, chips, tracer, max_seq_len,
              prefill_chunk=None):
        raise Built(max_seq_len, prefill_chunk)

    monkeypatch.setattr(stack, "build", build)
    cell = cells.load_cell(bench_tree.ROOT, workload)
    assert (cell.max_seq_len, cell.prefill_chunk) == (slot, chunk)
    args = argparse.Namespace(workload=workload, seed=BIG_SEED, seconds=1.0,
                              trace=0, control=0)
    with pytest.raises(Built) as got:
        bench_run.run(cell, args, jax.devices()[:1], out=lambda s: None)
    assert got.value.args == (slot, chunk)
