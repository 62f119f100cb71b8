#!/usr/bin/env python3
"""On-chip benchmark of EPARA serving: one cell, one run, one result line.

    python3 bench/run.py --workload minicpm-2b.streams --seed 7 \\
        --seconds 45 --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) is one model configuration
under one traffic mix.  A run builds the serving stack as the launcher
does (one server, the EPARA plan, one ``ServiceRuntime`` with the
category's default knobs but the slot length and prefill chunk a mix
sets, paged Pallas kernels; a ``(1, chips)`` mesh for a four-chip cell), with weights the benchmark draws from ``--seed`` on the
device.  It warms every shape the traffic reaches (set-up), then drives
``ClusterSupervisor.submit`` / ``step`` from its own clock for
``--seconds``.  The seed draws the weights and every prompt's token ids;
the schedule of lengths and arrivals is the mix's own, the same for every
seed (``harness/traffic.py``).  With ``--trace 0`` the result holds the end-to-end
metrics; with ``--trace 1`` the profiler records the last seconds of the
window and the result holds the per-layer metrics.  After the window the
served tokens of a seeded sample of finished requests are checked against
a plain float32 forward of the same weights (``correct``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``, each number compared beside its limit.  Without
a TPU, or with fewer chips than the cell asks for, it prints no result and
exits 2.

Layout (everything found by the names in ``BENCHMARK.json``):

    bench/configs/<config>.json   a configuration: arch id, model family,
                                  published sizes as run, max_seq_len,
                                  check limit
    bench/families/<family>.py    a model family: the program's config
                                  fields, sizes, weight layout, plain
                                  reference forward and its control, the
                                  operation and byte counts
    bench/traffic/<traffic>.json  a traffic mix: loop, rate or streams,
                                  length distributions; max_seq_len where
                                  its longest request needs another, and
                                  prefill_chunk where its deployment sets
                                  the plan's chunk
    bench/metrics/<metric>.py     a per-layer metric: ``read(rec)``
                                  returns a number, or None when the run
                                  holds nothing to read
    bench/harness/                the shared yardstick: generator, weight
                                  drawing, served-token check, trace
                                  reduction, roofline arithmetic, peak
                                  table
    bench/tests/                  CPU tests of the yardstick

To add a cell, add its ``workloads`` entry (and, where new, a
``configs`` entry with its file and a traffic file).  To add a model
family, add ``bench/families/<family>.py`` and a configuration that names
it.  To add a per-layer metric, add its ``per_layer`` entry and
``bench/metrics/<name>.py``.  No existing file changes.

``--control 1`` (never used by the benchmark's own runs) also reads the
lower-precision control over the same sample, for setting the limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import cells as cells_lib  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
CHECK_SAMPLE_TOKENS = 400       # served tokens compared, at least
PROFILE_DIR = ".bench_profile"
TRACER_CAPACITY = 1 << 21


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Profile:
    """Starts the profiler on demand; stopped after the window."""

    def __init__(self, log_dir: pathlib.Path, seconds: float):
        self.log_dir, self.seconds, self.running = log_dir, seconds, False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self.running = True

    def stop(self) -> None:
        import jax
        if self.running:
            jax.profiler.stop_trace()
            self.running = False


def check_sample(results, reqs, window, seed):
    """Finished requests of the window, seeded: the longest, then others
    in a seeded order until ``CHECK_SAMPLE_TOKENS`` served tokens."""
    import numpy as np
    done = [r for t, r in results if t >= window[0] and r.rid in reqs
            and reqs[r.rid].stream >= 0]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), r.rid))
    rest = done[1:]
    order = np.random.default_rng([seed, 11]).permutation(len(rest))
    picked, n = [done[0]], len(done[0].tokens)
    for i in order:
        if n >= CHECK_SAMPLE_TOKENS:
            break
        picked.append(rest[i])
        n += len(rest[i].tokens)
    return picked


def run(cell, args, devices, root: pathlib.Path = ROOT, out=print) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object."""
    import jax
    import numpy as np
    from repro.obs import Tracer
    from harness import devtrace, reference, stack as stack_lib
    from harness.driver import Driver
    from harness.peaks import peaks_for
    from harness.record import RunRecord, percentile

    platform = devices[0].platform
    if platform != "cpu" and "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    if platform != "cpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    config, mix = cell.config, cell.traffic
    tracer = Tracer(capacity=TRACER_CAPACITY)
    max_seq = cell.max_seq_len
    family = cell.family
    stack = stack_lib.build(config, family, args.seed, cell.chips, tracer,
                            max_seq, cell.prefill_chunk)
    rt = stack.runtime
    vocab = int(config["config"]["vocab_size"])
    driver = Driver(stack, mix, args.seed, vocab)
    compiles = {"window": 0, "all": 0}

    def on_event(event, duration, **_):
        if event in COMPILE_EVENTS:
            compiles["all"] += 1
            compiles["window"] += driver.in_window

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t_build = time.perf_counter()
    driver.compile_warmup(rt.chunk_buckets, max_seq)
    slots = stack_lib.arena_slots(stack)
    out(f"platform {platform}, {devices[0].device_kind}, {len(devices)} "
        f"device(s); arena {slots} slots of {max_seq} tokens, kv "
        f"{rt.kv_dtype}, chunk buckets {list(rt.chunk_buckets)}")

    profile = None
    if args.trace:
        profile = Profile(root / PROFILE_DIR / cell.name,
                          float(mix["profile_s"]))

    def first_token_seen(rids):
        seen = {int(e[2]) for e in tracer.events()
                if e[3] == "first_token" and e[2].isdigit()}
        return rids <= seen

    t_warm = time.perf_counter()
    if mix["loop"] == "closed":
        driver.run_closed(args.seconds, first_token_seen, profile)
    else:
        driver.run_open(args.seconds, profile)
    w0, w1 = driver.window
    setup_s = w0 - T_START
    if profile is not None:
        profile.stop()
    driver.settle(first_token_seen)
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0 for d in devices)
    out(f"set-up {setup_s:.3f} s: build {t_build - T_START:.3f} s, compile "
        f"warm-up {t_warm - t_build:.3f} s, traffic warm-up "
        f"{w0 - t_warm:.3f} s; after the window {driver.settled - w1:.3f} s "
        f"waiting for the window's first tokens")

    events = tracer.events()
    if tracer.dropped:
        raise RuntimeError(f"tracer ring dropped {tracer.dropped} events")
    dims = family.dims(config, rt.kv_dtype)
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if platform != "cpu" else {}
    rec = RunRecord(cell=cell.name, chips=cell.chips, family=family,
                    dims=dims, peaks=peaks, slots=slots, window=(w0, w1),
                    events=events, svc=stack.arch, reqs=driver.reqs,
                    steps=driver.steps,
                    profile_window=driver.profile_window,
                    settled=driver.settled)
    finished = [r for _, r in driver.results]
    rec.check_emissions(finished)
    sample = check_sample(driver.results, driver.reqs, (w0, w1), args.seed)
    attempted = sum(1 for r in driver.reqs.values() if w0 <= r.due <= w1)
    failed = sum(1 for rj in stack.supervisor.report.rejects
                 if rj.req.rid in driver.reqs
                 and w0 <= driver.reqs[rj.req.rid].due <= w1)
    ttft, itl = rec.ttfts(), rec.itls()
    waited = sum(1 for r, q in driver.reqs.items() if w0 <= q.due <= w1
                 and (r not in rec.timelines
                      or rec.timelines[r].first_token is None))
    out(f"window {w1 - w0:.3f} s: {attempted} requests due, {failed} "
        f"refused, {len([1 for t, _ in driver.results if w0 <= t <= w1])} "
        f"finished, {rec.tokens_in_window()} tokens, "
        f"{len(driver.steps)} rounds in all; requests waiting for a slot "
        f"at the window's start {driver.queue[0]}, end {driver.queue[1]}")
    out(f"ttft median {percentile(ttft, 50)} s, p90 {percentile(ttft, 90)} "
        f"s over {len(ttft)} requests due in the window ({waited} still "
        f"without a first token when waiting stopped); gap median "
        f"{percentile(itl, 50)} s, p90 {percentile(itl, 90)} s over "
        f"{len(itl)} gaps")
    due = [q for q in driver.reqs.values() if w0 <= q.due <= w1]
    out("ttft in order of arrival (s/prompt tokens): " + ", ".join(
        f"{t:.3f}/{q.prompt_len}" for q, t in zip(due, ttft)))
    waits = driver.waits
    quarter = [[(n, k) for t, n, k in waits
                if int(4 * (t - w0) / (w1 - w0)) == q] for q in range(4)]
    mean = lambda xs: f"{sum(xs) / len(xs):.1f}" if xs else "-"
    out(f"requests waiting for a first token (queued or prefilling) after "
        f"the window's first round {waits[0][1]}, after its last "
        f"{waits[-1][1]}; mean after each round, per quarter of the window: "
        f"requests " + ", ".join(mean([n for n, _ in q]) for q in quarter)
        + "; their prompt tokens still to prefill " + ", ".join(
            mean([k for _, k in q]) for q in quarter))
    # what a stall would leave: a quarter with fewer rounds, a long round,
    # a long collection
    rounds = [(a, b - a) for a, b in driver.steps if w0 <= a < w1]
    per_quarter = [sum(1 for a, _ in rounds
                       if int(4 * (a - w0) / (w1 - w0)) == k)
                   for k in range(4)]
    gc_long = max(driver.gc_pauses, key=lambda p: p[1], default=(0, 0.0, None))
    a, d = max(rounds, key=lambda r: r[1], default=(0.0, 0.0))
    inside = sorted(((t1 - t0, f"{pid}/{tid}/{name}")
                     for kind, pid, tid, name, t0, t1, _ in tracer.events()
                     if kind == "X" and a <= t0 and t1 <= a + d
                     and t1 - t0 > 0.05), reverse=True)[:6]
    out(f"rounds per quarter of the window {per_quarter}, longest round "
        f"{d!r} s (its spans over 50 ms: " + ", ".join(
            f"{n} {t:.3f} s" for t, n in inside) + f"); garbage "
        f"collections in the window {len(driver.gc_pauses)}, longest "
        f"{gc_long[1]!r} s (generation {gc_long[2]})")
    late = [q.submit - q.due for q in driver.reqs.values()
            if w0 <= q.due <= w1]
    out(f"generator lateness (sent less due) over {len(late)} requests: "
        f"median {percentile(late, 50)} s, max {max(late, default=None)} s")
    out(f"compiles inside the window: {compiles['window']} "
        f"(set-up: {compiles['all'] - compiles['window']})")

    # the program's state goes before the reference runs
    params = stack.params
    stack.free_program_state()
    driver.stack = None
    del rt
    gc.collect()
    ref_kw = dict(width=max_seq, served_max=int(mix["output_len"]["max"]))
    pairs = [(driver.reqs[r.rid].prompt, np.asarray(r.tokens, np.int32))
             for r in sample]
    gaps = reference.served_gaps(params, family.logits, dims, pairs,
                                 **ref_kw)
    compared = int(sum(g.size for g in gaps))
    worst = float(max((g.max() for g in gaps), default=float("inf")))
    limit = float(config["check"]["worst_gap_sigma"])
    min_tokens = int(config["check"]["min_tokens_compared"])
    correct = bool(compared >= min_tokens and worst <= limit)
    out(f"reference check: {compared} served tokens of {len(pairs)} "
        f"requests; worst gap {worst!r} sigma (limit {limit})")
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.control:
        cg = reference.served_gaps(params, family.logits, dims, pairs,
                                   control=True, **ref_kw)
        ctl = float(max((g.max() for g in cg), default=float("inf")))
        out(f"control (fp8) worst gap {ctl!r} sigma over "
            f"{int(sum(g.size for g in cg))} positions")
        result["control"] = {"worst_gap_sigma": ctl,
                             "correct": bool(ctl <= limit)}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    breakdown = None
    if args.trace:
        prof = devtrace.load(str(root / PROFILE_DIR / cell.name))
        rec.profile = prof
        metrics = {}
        for m in cell.per_layer:
            v = m.read(rec)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
        p0, p1 = driver.profile_window
        off = prof.host_offset(driver.steps, driver.profile_window)
        busy = [devtrace.length(devtrace.clip(devtrace.merge(
            (o.start, o.end) for o in d.ops), p0 + off, p1 + off))
            for d in prof.devices]
        if busy:
            device["busy_s"] = sum(busy) / len(busy)
            device["window_s"] = p1 - p0
            breakdown = breakdown_of(rec, prof, driver.steps)
    else:
        e2e = {"ttft_p50_s": percentile(ttft, 50),
               "ttft_p90_s": percentile(ttft, 90),
               "itl_p90_ms": (None if not itl
                              else 1e3 * percentile(itl, 90)),
               "output_tokens_per_s": rec.tokens_in_window() / (w1 - w0),
               "setup_s": setup_s}
        # ``<name>.<suffix>`` reads what ``<name>`` reads, in the cells
        # whose spread needs a bound of its own
        metrics = {m.name: {"value": e2e[m.name.split(".")[0]],
                            "unit": m.unit}
                   for m in cell.end_to_end
                   if e2e.get(m.name.split(".")[0]) is not None}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {
        "worst_gap_sigma": {"value": worst, "limit": limit},
        "tokens_compared_min": {"value": compared, "limit": min_tokens}}
    return result


def breakdown_of(rec, prof, steps) -> dict:
    """Device ops that took most time, and the longest idle gaps by what
    the host was doing (harness annotations and engine phases)."""
    from harness import devtrace
    p0, p1 = rec.profile_window
    off = prof.host_offset(steps, rec.profile_window)
    n = len(prof.devices)
    tot: dict = {}
    for d in prof.devices:
        ops = [o for o in d.ops if o.end > p0 + off and o.start < p1 + off]
        for k, t in devtrace.op_totals(ops).items():
            tot[k] = tot.get(k, 0.0) + t / n
    spans = [(o.name, o.start, o.end) for o in prof.host]
    spans += [("engine." + name, a + off, b + off)
              for name, a, b in rec.phases if a >= p0 - 1 and b <= p1 + 1]
    idle: dict = {}
    for d in prof.devices:
        merged = devtrace.merge((o.start, o.end) for o in d.ops)
        for k, v in devtrace.attribute(
                devtrace.gaps(merged, p0 + off, p1 + off), spans).items():
            idle[k] = idle.get(k, 0.0) + v / n
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(tot), "idle_gaps": top(idle)}


def main(argv=None) -> int:
    args = parse(argv)
    cell = cells_lib.load_cell(ROOT, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    result = run(cell, args, devices[:cell.chips],
                 out=lambda s: print(s, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
