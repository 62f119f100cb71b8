"""Observability layer (``repro/obs``): byte-inertness of the disabled
path, span-tree invariants of the request-lifecycle tracer (including
park->resume and speculative rounds), metrics exposition round-trips,
and the telemetry -> ``SimConfig`` calibration loop.

The headline acceptance gate: obs OFF (the default) must leave emitted
greedy tokens bit-identical and compile counts unchanged versus obs ON —
the tracer and registry are host-side annotators, never participants.
"""
import dataclasses
import glob
import json
import math
import os

import jax
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import toy_config
from repro.core.allocator import ParallelPlan
from repro.core.categories import Sensitivity, TaskCategory
from repro.models import transformer as T
from repro.obs import (NULL_TRACER, Histogram, MetricsRegistry,
                       ServiceTelemetry, Tracer, calibrate, merge_telemetry,
                       parse_prometheus_text, telemetry_from_runtime,
                       telemetry_from_snapshot, telemetry_from_steps,
                       validate_chrome_trace)
from repro.serving.engine import GenerationRequest, ServiceRuntime
from repro.simulator.engine import SimConfig

LAT = TaskCategory(Sensitivity.LATENCY, False)
FREQ = TaskCategory(Sensitivity.FREQUENCY, False)

# the hypothesis interleaving test drives a real engine per example, so
# its budget is its own knob (the CI hypothesis job raises it)
OBS_EXAMPLES = int(os.environ.get("OBS_EXAMPLES", "5"))


_TOY = None


def _toy_params():
    """Module-level memo (not a fixture): the hypothesis fallback shim
    cannot inject pytest fixtures into ``@given`` tests."""
    global _TOY
    if _TOY is None:
        cfg = toy_config()
        _TOY = (cfg, T.init(jax.random.PRNGKey(0), cfg))
    return _TOY


@pytest.fixture(scope="module")
def toy():
    return _toy_params()


def _runtime(toy, *, bs=4, category=LAT, admission=None, **kw):
    cfg, params = toy
    plan = ParallelPlan(service="toy", category=category, bs=bs)
    if admission is not None:
        plan = dataclasses.replace(plan, admission=admission)
    return ServiceRuntime(cfg, params, plan, **kw)


def _reqs(n, *, seed=0, max_new=4):
    rng = np.random.default_rng(seed)
    return [GenerationRequest(
        rid=i, tokens=rng.integers(1, 257, 5 + i % 3).astype(np.int32),
        max_new_tokens=max_new) for i in range(n)]


def _serve(rt, reqs):
    for r in reqs:
        rt.submit(r)
    return {r.rid: tuple(int(x) for x in r.tokens) for r in rt.drain()}


def _flatten(spans):
    out = []
    for s in spans:
        out.append(s)
        out.extend(_flatten(s.children))
    return out


def _check_tree(s, lo=-math.inf, hi=math.inf):
    """Balanced-tree invariants: every span's interval is well-formed,
    inside its parent, and siblings start in monotonic order."""
    assert lo <= s.start <= s.end <= hi, (s.name, s.start, s.end, lo, hi)
    t = s.start
    for c in s.children:
        assert c.start >= t, (s.name, c.name, c.start, t)
        _check_tree(c, s.start, s.end)
        t = c.start


@pytest.fixture(scope="module")
def basic_run(toy):
    """One traced + metered serve shared by the lifecycle tests."""
    tracer, metrics = Tracer(), MetricsRegistry()
    rt = _runtime(toy, tracer=tracer, metrics=metrics)
    toks = _serve(rt, _reqs(4, seed=1))
    return rt, tracer, metrics, toks


@pytest.fixture(scope="module")
def spec_run(toy):
    """A speculative (self-draft) serve with obs on, plus the recorded
    per-step ``StepStats`` — feeds the span and calibration tests."""
    cfg, params = toy
    tracer, metrics = Tracer(), MetricsRegistry()
    rt = ServiceRuntime(cfg, params,
                        ParallelPlan(service="toy", category=LAT, bs=4),
                        kvcache_impl="paged", draft_params=params,
                        draft_cfg=cfg, speculate=3,
                        tracer=tracer, metrics=metrics)
    rng = np.random.default_rng(3)
    for i in range(3):
        rt.submit(GenerationRequest(
            rid=i, tokens=rng.integers(1, cfg.vocab_size,
                                       6 + 2 * i).astype(np.int32),
            max_new_tokens=8))
    steps = []
    while rt.pending() or rt.in_flight():
        steps.append(rt.step())
    return rt, tracer, metrics, steps


# ---------------------------------------------------------------------
# byte-inertness: obs off == obs on, to the bit and to the compile
# ---------------------------------------------------------------------
def test_obs_disabled_is_byte_inert(toy):
    def run(**obs_kw):
        rt = _runtime(toy, **obs_kw)
        return (_serve(rt, _reqs(6, seed=2)), rt.decode_traces,
                rt.prefill_traces)

    plain = run()
    tracer = Tracer()
    traced = run(tracer=tracer, metrics=MetricsRegistry())
    assert plain[0] == traced[0]        # bit-identical greedy tokens
    assert plain[1:] == traced[1:]      # identical compile counts
    assert plain[1] == 1                # and still exactly one decode trace
    # ... with the scoped spans and device waits recorded
    waits = {e[3] for e in tracer.events() if e[2] == "wait"}
    assert {"decode", "first_token"} <= waits


# ---------------------------------------------------------------------
# lifecycle span trees
# ---------------------------------------------------------------------
def test_request_lifecycle_span_tree(basic_run):
    rt, tracer, _, toks = basic_run
    for rid, tokens in toks.items():
        tid = str(rid)
        assert tracer.open_spans("toy", tid) == []   # balanced
        roots, instants = tracer.span_tree("toy", tid)
        assert len(roots) == 1 and roots[0].name == "request"
        names = [c.name for c in roots[0].children]
        assert names == ["queued", "prefill", "decode"]
        assert roots[0].args.get("outcome") == "served"
        decode = roots[0].children[-1]
        assert decode.args.get("tokens") == len(tokens)
        assert [i.name for i in instants] == ["first_token"]
        assert roots[0].children[1].end <= instants[0].start + 1e-9
        _check_tree(roots[0])


def test_engine_phase_timeline(basic_run):
    _, tracer, _, _ = basic_run
    assert ("toy", "engine") in tracer.timelines()
    phases = [e for e in tracer.events() if e[2] == "engine"]
    names = {e[3] for e in phases}
    assert {"step", "evict", "admit", "fused_decode"} <= names
    # every phase is a finished complete event with non-negative duration
    assert all(e[0] == "X" and e[5] >= e[4] for e in phases)
    # one "step" span per scheduling round, covering its sub-phases
    steps = [e for e in phases if e[3] == "step"]
    assert len(steps) >= 4


def test_park_resume_span_sequence(toy):
    """SDF preemption parks a straggler mid-decode; its timeline must
    read decode -> parked -> decode with the resume annotated."""
    cfg, params = toy
    tracer = Tracer()
    rt = _runtime(toy, bs=2, admission="sdf", tracer=tracer)
    rng = np.random.default_rng(7)
    t = 0.0

    def drain():
        nonlocal t
        while rt.pending() or rt.in_flight():
            rt.step(now=t)
            t += 1.0
            assert t < 5000.0, "engine failed to drain"

    # warmup teaches the controller the round clock (cold SDF is FIFO)
    for i in range(2):
        rt.submit(GenerationRequest(
            rid=1000 + i,
            tokens=rng.integers(1, cfg.vocab_size, 4).astype(np.int32),
            max_new_tokens=4), now=t)
    drain()
    # two deadline-less stragglers fill both slots...
    for i in range(2):
        rt.submit(GenerationRequest(
            rid=i, tokens=rng.integers(1, cfg.vocab_size,
                                       6).astype(np.int32),
            max_new_tokens=24), now=t)
    for _ in range(2):
        rt.step(now=t)
        t += 1.0
    # ...then urgent deadlined shorts force a park
    for i in range(4):
        rt.submit(GenerationRequest(
            rid=100 + i,
            tokens=rng.integers(1, cfg.vocab_size, 4).astype(np.int32),
            max_new_tokens=4, deadline_s=t + 14.0), now=t)
        for _ in range(3):
            rt.step(now=t)
            t += 1.0
    drain()
    assert rt.admission.preemptions >= 1
    parked_tids = [
        tid for pid, tid in tracer.timelines()
        if pid == "toy" and tid != "engine"
        and any(s.name == "parked"
                for s in _flatten(tracer.span_tree("toy", tid)[0]))]
    assert parked_tids
    for tid in parked_tids:
        roots, _ = tracer.span_tree("toy", tid)
        assert len(roots) == 1 and roots[0].name == "request"
        _check_tree(roots[0])
        seq = [c.name for c in roots[0].children]
        for j, name in enumerate(seq):
            if name == "parked":
                assert seq[j - 1] == "decode" and seq[j + 1] == "decode"
        assert any(c.name == "decode" and c.args.get("resumed")
                   for c in roots[0].children)


def test_speculative_round_spans(spec_run):
    rt, tracer, _, _ = spec_run
    assert rt.verify_launches > 0
    rounds = []
    for i in range(3):
        roots, _ = tracer.span_tree("toy", str(i))
        assert len(roots) == 1 and roots[0].name == "request"
        _check_tree(roots[0])
        decodes = [c for c in roots[0].children if c.name == "decode"]
        assert decodes
        rounds += [g for d in decodes for g in d.children
                   if g.name == "spec_round"]
    assert rounds
    assert all("accepted" in g.args and g.args["k"] == 3 for g in rounds)
    engine = {s.name
              for s in _flatten(tracer.span_tree("toy", "engine")[0])}
    assert "verify" in engine and "step" in engine


@settings(max_examples=OBS_EXAMPLES, deadline=None)
@given(specs=st.lists(
    st.tuples(st.integers(3, 10),     # prompt length
              st.integers(1, 6),      # max_new_tokens
              st.integers(0, 3)),     # engine rounds before next submit
    min_size=1, max_size=6))
def test_random_interleavings_yield_wellformed_trees(specs):
    """Property: ANY interleaving of submissions and scheduling rounds
    leaves every request timeline balanced (no open spans), rooted at a
    single ``request`` span, with monotonic properly-nested children."""
    tracer = Tracer()
    rt = _runtime(_toy_params(), bs=2, tracer=tracer)
    rng = np.random.default_rng(0)
    for rid, (plen, max_new, gap) in enumerate(specs):
        rt.submit(GenerationRequest(
            rid=rid, tokens=rng.integers(1, 257, plen).astype(np.int32),
            max_new_tokens=max_new))
        for _ in range(gap):
            rt.step()
    rt.drain()
    for rid in range(len(specs)):
        tid = str(rid)
        assert tracer.open_spans("toy", tid) == []
        roots, instants = tracer.span_tree("toy", tid)
        assert len(roots) == 1 and roots[0].name == "request"
        _check_tree(roots[0])
        assert all(roots[0].start <= i.start <= roots[0].end
                   for i in instants)


# ---------------------------------------------------------------------
# tracer primitives
# ---------------------------------------------------------------------
def test_tracer_ring_bound_and_close_semantics():
    ticks = iter(range(1000))
    tr = Tracer(capacity=4, clock=lambda: float(next(ticks)))
    # close() ends every open span innermost-first, args on the outermost
    tr.begin("p", "1", "request")
    tr.begin("p", "1", "queued")
    tr.close("p", "1", verdict="REJECT")
    roots, _ = tr.span_tree("p", "1")
    assert [s.name for s in roots] == ["request"]
    assert roots[0].args == {"verdict": "REJECT"}
    assert roots[0].children[0].name == "queued"
    assert tr.open_spans("p", "1") == []
    tr.end("p", "1")                    # end with nothing open: no-op
    # ring bound: oldest events drop, counters keep the truth
    for i in range(8):
        tr.instant("p", "1", f"i{i}")
    assert len(tr.events()) == 4
    assert tr.dropped == 6              # 2 spans + 8 instants, cap 4
    assert tr.emitted == 10


def test_chrome_trace_export_and_validation(basic_run, tmp_path):
    _, tracer, _, toks = basic_run
    path = tmp_path / "trace.json"
    tracer.export(str(path))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) == len(doc["traceEvents"])
    thread_names = {ev["args"]["name"] for ev in doc["traceEvents"]
                    if ev.get("ph") == "M"
                    and ev["name"] == "thread_name"}
    assert {str(r) for r in toks} <= thread_names
    assert "engine" in thread_names
    with pytest.raises(ValueError):
        validate_chrome_trace({})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "ts": 0, "pid": 1, "tid": 1}]})


def test_scoped_span_nests_and_balances():
    ticks = iter(range(1000))
    tr = Tracer(clock=lambda: float(next(ticks)))
    with tr.span("p", "engine", "step", round=1) as outer:
        with tr.span("p", "engine", "admit") as sp:
            sp.set(admitted=2)
        with tr.span("p", "wait", "decode", live=3):
            pass
        outer.set(pending=0)
    with pytest.raises(RuntimeError):
        with tr.span("p", "engine", "chunk"):
            raise RuntimeError("the span still closes")
    roots, _ = tr.span_tree("p", "engine")
    assert [r.name for r in roots] == ["step", "chunk"]
    step = roots[0]
    assert step.args == {"round": 1, "pending": 0}
    assert [(c.name, c.args) for c in step.children] == [
        ("admit", {"admitted": 2})]
    _check_tree(step)
    (wait,), _ = tr.span_tree("p", "wait")
    assert wait.args == {"live": 3}
    assert step.start < wait.start <= wait.end < step.end


def test_null_tracer_span_is_a_shared_noop():
    a = NULL_TRACER.span("p", "engine", "step", live=1)
    assert a is NULL_TRACER.span("p", "wait", "decode")
    with a as sp:
        sp.set(keys=4)
        with NULL_TRACER.span("p", "engine", "admit") as inner:
            assert inner is sp


def _host_annotations(log_dir):
    """(name, start_ns, end_ns) of the ``epara.*`` host annotations of
    the profile written under ``log_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return sorted(
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("epara."))


def test_round_spans_are_profiler_annotations(tmp_path):
    """A ``jax.profiler`` capture of a few supervised rounds holds the
    engine phases, device waits and control-plane spans as host
    annotations, one for each ring event, and each wait lies inside its
    phase."""
    from repro.core import EdgeCloudControlPlane, ServerSpec, ServiceSpec
    from repro.serving.engine import EparaServingEngine
    from repro.serving.failover import ClusterSupervisor
    cfg, params = _toy_params()
    cp = EdgeCloudControlPlane(
        [ServerSpec(sid=0, num_gpus=2)],
        {"toy": ServiceSpec("toy", flops_per_request=1e10,
                            weights_bytes=2e8, vram_bytes=5e8,
                            slo_latency_s=100.0)})
    cp.run_placement({("toy", 0): 10.0})
    tracer = Tracer()
    rt = ServiceRuntime(cfg, params, cp.plans["toy"], block_size=16,
                        prefill_chunk=16, tracer=tracer)
    eng = EparaServingEngine()
    eng.deploy("toy", rt)
    sup = ClusterSupervisor(cp, {0: eng})
    rng = np.random.default_rng(5)

    def submit(rid, n):
        sup.submit("toy", GenerationRequest(
            rid=rid, tokens=rng.integers(1, 257, n).astype(np.int32),
            max_new_tokens=3), at_server=0, now=0.0)

    submit(100, 40)                      # compile every shape first
    sup.run_until_idle()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    n0 = len(tracer.events())
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    submit(1, 40)
    submit(2, 9)
    for _ in range(6):
        sup.step(0.0)
    jax.profiler.stop_trace()
    ring = [e for e in tracer.events()[n0:]
            if e[2] in ("engine", "wait", "control")]
    ann = _host_annotations(str(tmp_path))
    names = {a[0] for a in ann}
    assert {"epara.engine.step", "epara.engine.chunk",
            "epara.engine.fused_decode", "epara.engine.sample",
            "epara.wait.decode", "epara.wait.chunk", "epara.wait.first_token",
            "epara.control.step", "epara.control.publish",
            "epara.control.sync"} <= names
    assert sorted(f"epara.{e[2]}.{e[3]}" for e in ring) == \
        sorted(a[0] for a in ann)
    phase_of = {"decode": "fused_decode", "chunk": "chunk",
                "first_token": "chunk"}
    for name, a0, a1 in ann:
        if not name.startswith("epara.wait."):
            continue
        phase = "epara.engine." + phase_of[name.rsplit(".", 1)[1]]
        assert any(p == phase and p0 <= a0 and a1 <= p1
                   for p, p0, p1 in ann), name
    for name, a0, a1 in ann:
        if name.startswith("epara.engine."):
            assert any(p == "epara.control.step" and p0 <= a0 and a1 <= p1
                       for p, p0, p1 in ann), name


# ---------------------------------------------------------------------
# metrics: bucket math + exposition round-trips
# ---------------------------------------------------------------------
def test_histogram_bucket_math():
    h = Histogram("h", "t", buckets=(1, 2, 4))
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v, service="s")
    val = h.value(service="s")
    assert val["buckets"] == {"1": 2, "2": 2, "4": 3, "+Inf": 4}
    assert val["count"] == 4 and val["sum"] == pytest.approx(104.5)
    # cumulative counts are monotone by construction in the exposition
    lines = h.expose()
    bucket_counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines
                     if "_bucket" in ln]
    assert bucket_counts == sorted(bucket_counts)


def test_prometheus_roundtrip(basic_run):
    rt, _, metrics, toks = basic_run
    parsed = parse_prometheus_text(metrics.prometheus_text())
    assert parsed['epara_requests_finished_total{service="toy"}'] \
        == len(toks)
    assert parsed['epara_tokens_generated_total{service="toy"}'] \
        == sum(len(t) for t in toks.values())
    assert parsed['epara_ttft_seconds_count{service="toy"}'] == len(toks)
    assert parsed['epara_decode_compiles{service="toy"}'] \
        == rt.decode_traces == 1
    assert parsed['epara_ttft_seconds_bucket{service="toy",le="+Inf"}'] \
        == len(toks)
    with pytest.raises(ValueError):
        parse_prometheus_text("")
    with pytest.raises(ValueError):
        parse_prometheus_text('broken{label="x" 3')


# ---------------------------------------------------------------------
# calibration: telemetry -> SimConfig
# ---------------------------------------------------------------------
def test_ttft_counts_from_submit(toy):
    """One slot, three requests: the later ones wait in the queue for
    whole requests, and their time to first token includes that wait."""
    metrics = MetricsRegistry()
    rt = _runtime(toy, bs=1, metrics=metrics)
    _serve(rt, _reqs(3, seed=4, max_new=6))
    ttft = metrics.histogram("ttft_seconds").value(service="toy")
    wait = metrics.histogram("queue_wait_seconds").value(service="toy")
    assert ttft["count"] == wait["count"] == 3
    assert ttft["sum"] > wait["sum"] > 0.0


def test_calibration_steps_and_runtime_agree(spec_run):
    rt, _, _, steps = spec_run
    a = telemetry_from_steps("toy", steps, spec_k=3)
    b = telemetry_from_runtime("toy", rt)
    assert a.accepted_tokens == b.accepted_tokens == rt.accepted_tokens
    assert a.verify_launches == b.verify_launches == rt.verify_launches
    assert a.prefill_tokens_computed == b.prefill_tokens_computed
    assert a.prefill_seconds == pytest.approx(b.prefill_seconds)
    cal = calibrate({"toy": b})
    per_launch = rt.accepted_tokens / rt.verify_launches
    expected = min(1.0, max(0.0, (per_launch - 1.0) / 3))
    assert cal.spec_accept_rate == pytest.approx(expected)
    assert expected > 0.5       # a self-draft accepts nearly every token


def test_calibration_snapshot_roundtrip(spec_run):
    rt, _, metrics, _ = spec_run
    tel = telemetry_from_snapshot(metrics.snapshot())
    assert "toy" in tel
    s, d = tel["toy"], telemetry_from_runtime("toy", rt)
    assert (s.spec_k, s.accepted_tokens, s.verify_launches) \
        == (d.spec_k, d.accepted_tokens, d.verify_launches)
    assert s.prefill_tokens_computed == d.prefill_tokens_computed
    assert s.prefix_hit_tokens == d.prefix_hit_tokens
    assert s.prefill_seconds == pytest.approx(d.prefill_seconds)
    assert s.spec_accept_rate == pytest.approx(d.spec_accept_rate)


def test_calibration_prefix_hit_rate(toy):
    cfg, params = toy
    rt = _runtime(toy, category=FREQ, kvcache_impl="paged",
                  max_seq_len=160, block_size=16)
    rng = np.random.default_rng(11)
    prefix = rng.integers(1, cfg.vocab_size, 64).astype(np.int32)

    def wave(rids):
        for i in rids:
            rt.submit(GenerationRequest(
                rid=i, tokens=np.concatenate([
                    prefix, rng.integers(1, cfg.vocab_size,
                                         16).astype(np.int32)]),
                max_new_tokens=4))
        rt.drain()

    wave([0])                   # warm request populates the cache
    wave(range(1, 5))           # the repeated-prefix wave hits it
    assert rt.prefix_hit_tokens > 0
    tel = telemetry_from_runtime("toy", rt)
    expected = rt.prefix_hit_tokens / (rt.prefix_hit_tokens
                                       + rt.prefill_tokens_computed)
    assert tel.prefix_hit_rate == pytest.approx(expected)
    cal = calibrate({"toy": tel}, base=SimConfig(prefill_token_s=2e-4))
    assert cal.prefix_hit_rates["toy"] == pytest.approx(expected)
    assert 0.0 < cal.prefix_hit_rates["toy"] < 1.0
    assert cal.prefill_token_s > 0.0    # measured, replacing the base


def test_calibration_cold_run_keeps_base():
    """A run that measured nothing calibrates to exactly the base
    config — the loop is safe to run unconditionally."""
    base = SimConfig(spec_accept_rate=0.7, prefill_token_s=2e-4,
                     prefix_hit_rates={"svc": 0.5})
    cal = calibrate({"svc": ServiceTelemetry("svc")}, base=base)
    assert cal.spec_accept_rate == 0.7
    assert cal.prefill_token_s == 2e-4
    assert dict(cal.prefix_hit_rates) == {"svc": 0.5}


def test_merge_telemetry_sums_and_guards_spec_k():
    a = ServiceTelemetry("s", spec_k=3, accepted_tokens=8,
                         verify_launches=2, prefix_hit_tokens=10,
                         prefill_tokens_computed=30, prefill_seconds=0.3,
                         decode_steps=5)
    b = ServiceTelemetry("s", spec_k=3, accepted_tokens=4,
                         verify_launches=1, prefix_hit_tokens=2,
                         prefill_tokens_computed=10, prefill_seconds=0.1,
                         decode_steps=2)
    m = merge_telemetry([a, b])
    assert set(m) == {"s"}
    assert m["s"].accepted_tokens == 12
    assert m["s"].verify_launches == 3
    assert m["s"].prefix_hit_tokens == 12
    assert m["s"].prefill_tokens_computed == 40
    assert m["s"].prefill_seconds == pytest.approx(0.4)
    assert a.accepted_tokens == 8       # inputs are copied, not mutated
    with pytest.raises(ValueError):
        merge_telemetry([a, ServiceTelemetry("s", spec_k=2,
                                             verify_launches=1)])
