"""The trace reduction: busy union, per-kernel and per-program time, gap
attribution to host spans; on synthetic device
events and on a profile recorded here."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import bench_tree  # noqa: F401
from harness import devtrace, layers
from harness.devtrace import Device, DeviceTrace, Op


def test_union_gaps_and_overlap():
    merged = devtrace.merge([(0, 2), (1, 3), (5, 6), (6, 7), (10, 11)])
    assert merged == [(0, 3), (5, 7), (10, 11)]
    assert devtrace.length(merged) == 6
    assert devtrace.gaps(merged, 1, 12) == [(3, 5), (7, 10), (11, 12)]
    assert devtrace.clip(merged, 2, 10.5) == [(2, 3), (5, 7), (10, 10.5)]


def test_gaps_go_to_the_innermost_host_span():
    spans = [("bench.step", 0, 10), ("engine.sample", 2, 4),
             ("bench.wait", 12, 20)]
    out = devtrace.attribute([(2.5, 3.5), (5, 6), (13, 15), (25, 26)], spans)
    assert out == {"engine.sample": 1.0, "bench.step": 1.0,
                   "bench.wait": 2.0, "no host span": 1.0}


@dataclasses.dataclass
class _Rec:
    profile: object
    profile_window: tuple
    steps: list
    chips: int = 1


def _synthetic():
    """Two decode runs of 10 s with a 4 s kernel each, one chunk run,
    on a profile clock 100 s ahead of the harness's."""
    k1 = "%closed_call.1 = bf16[1512,8,64]{2,1,0} custom-call(s32[42,32])"
    k7 = "%closed_call.7 = bf16[288,128,64]{2,1,0} custom-call(s32[1,32])"
    f2 = "%fusion.2 = bf16[42,2304]{1,0} fusion(bf16[42,2304])"
    ar = "%all-reduce.3 = bf16[1,2304]{1,0} all-reduce(bf16[1,2304])"
    ops = [Op(k1, 101, 105), Op(f2, 105, 109), Op(k1, 121, 125),
           Op(f2, 125, 129), Op(k7, 141, 142), Op(ar, 142, 143)]
    mods = [Op("jit__step(3)", 100, 110), Op("jit__step(3)", 120, 130),
            Op("jit__chunk(5)", 140, 145)]
    dev = Device("/device:TPU:0", ops, mods)
    host = [Op("bench.step", 99, 111), Op("bench.step", 119, 131),
            Op("bench.step", 139, 146)]
    return DeviceTrace([dev], host)


def test_program_and_kernel_time_per_run():
    # the harness's steps after the profiled window (waiting for the
    # window's first tokens) are in no trace
    rec = _Rec(_synthetic(), (-1.0, 46.0),
               [(-30, -20), (-1, 11), (19, 31), (39, 46), (50, 52), (53, 60)])
    assert rec.profile.host_offset(rec.steps, rec.profile_window) == \
        pytest.approx(100.0)
    assert layers.mean_time_per_run(rec, layers.DECODE_PROGRAM) == 10.0
    assert layers.mean_time_per_run(rec, layers.DECODE_PROGRAM,
                                    layers.DECODE_KERNEL) == 4.0
    assert layers.mean_time_per_run(rec, layers.CHUNK_PROGRAM,
                                    layers.CHUNK_KERNEL) == 1.0
    runs = layers.program_runs(rec, layers.CHUNK_PROGRAM)[0]
    inside = layers.ops_inside(rec.profile.devices[0].ops, runs)
    assert [devtrace.label(o.name) for o in inside] == [
        "custom-call bf16[288,128,64]", "all-reduce bf16[1,2304]"]


def test_leaf_ops_add_up_by_kind_and_shape():
    w = "%while.2 = (s32[], bf16[42,2304]{1,0}) while(%tuple.1)"
    c1 = "%copy.135 = s8[1,1345,32,36,64]{4,3,2,1,0:T(8,128)(4,1)} copy(%p)"
    c2 = "%copy.132 = s8[1,1345,32,36,64]{4,3,2,1,0:T(8,128)(4,1)} copy(%q)"
    tot = devtrace.op_totals([Op(w, 0, 10), Op(c1, 1, 3), Op(c2, 4, 5)])
    assert tot == {"copy s8[1,1345,32,36,64]": 3.0}


def test_recorded_profile_has_the_harness_spans(tmp_path):
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    prof = devtrace.load(str(tmp_path))
    steps = [o for o in prof.host if o.name == "bench.step"]
    assert len(steps) == 3
    assert all(b.start >= a.end for a, b in zip(steps, steps[1:]))
