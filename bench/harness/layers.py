"""Readings shared by the per-layer metric readers (``bench/metrics``).

Device times are taken inside the profiled part of the window, per device,
and averaged over the devices the cell uses; counts of model work come
from the program's tracer over the same part of the window, moved onto
the profile's clock through the harness's ``bench.step`` spans.
"""
from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from . import devtrace

# Names the trace gives the programs and kernels of the serving path (seen
# on a v5e): the fused decode step and the chunked-prefill step are the
# jitted functions ``_step`` and ``_chunk`` of ``serving/engine.py``
# (``jit__step(<hash>)``, ``jit__chunk(<hash>)`` on ``XLA Modules``).  An
# op's name on ``XLA Ops`` is its HLO text, and a Pallas kernel shows only
# as a ``custom-call`` with no kernel name; inside those two programs the
# paged attention kernel (bf16 or int8) is the only custom call.
DECODE_PROGRAM = r"^jit__step\b"
CHUNK_PROGRAM = r"^jit__chunk\b"
DECODE_KERNEL = CHUNK_KERNEL = r" custom-call\("


def profiled(rec) -> Optional[Tuple[float, float, float]]:
    """(lo, hi, offset): the profiled part of the window on the profile's
    clock, and the offset from ``perf_counter`` to it."""
    if rec.profile is None or rec.profile_window is None \
            or not rec.profile.devices:
        return None
    p0, p1 = rec.profile_window
    off = rec.profile.host_offset(rec.steps, rec.profile_window)
    return p0 + off, p1 + off, off


def program_runs(rec, pattern: str) -> List[List[devtrace.Op]]:
    """Per device: executions of the programs matching ``pattern`` that
    lie inside the profiled part of the window."""
    lo, hi, _ = profiled(rec)
    return [[m for m in d.modules_matching(pattern)
             if m.start >= lo and m.end <= hi] for d in rec.profile.devices]


def ops_inside(ops: List[devtrace.Op],
               runs: List[devtrace.Op]) -> List[devtrace.Op]:
    """The ops that lie inside one of ``runs`` (non-overlapping)."""
    runs = sorted(runs, key=lambda r: r.start)
    starts = [r.start for r in runs]
    out = []
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.end <= runs[i].end:
            out.append(o)
    return out


def mean_time_per_run(rec, program: str,
                      kernel: Optional[str] = None) -> Optional[float]:
    """Mean device seconds per execution of ``program`` (or, with
    ``kernel``, of that kernel's ops inside those executions), averaged
    over devices; None where the profile holds no execution."""
    per_dev = []
    for d, runs in zip(rec.profile.devices, program_runs(rec, program)):
        if not runs:
            continue
        if kernel is None:
            t = sum(r.dur for r in runs)
        else:
            t = sum(o.dur for o in ops_inside(d.matching(kernel), runs))
            if t == 0.0:
                continue
        per_dev.append(t / len(runs))
    return sum(per_dev) / len(per_dev) if per_dev else None


def share(rec, ops: float, byt: float, seconds: float) -> float:
    """Roofline share, in percent, of per-device work over a device time;
    ``ops`` and ``byt`` are the whole model's, split over the chips."""
    from .counts import roofline_share
    pct, _bound = roofline_share(ops / rec.chips, byt / rec.chips, seconds,
                                 rec.peaks["bf16_flops"],
                                 rec.peaks["hbm_bytes_per_s"])
    return pct
