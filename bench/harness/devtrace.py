"""Reduce a profiler trace to device busy time, op and program time, and
the host activity behind each idle gap.

``jax.profiler`` writes ``<dir>/plugins/profile/<run>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Each accelerator is a plane named
``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per executed
operation and whose ``XLA Modules`` line holds one event per executed
program; the host's ``jax.profiler.TraceAnnotation`` spans sit on the
host plane.  Times here are seconds on the profile's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Op]                       # name: the op's HLO text
    modules: List[Op]                   # name: jit__<function>(<hash>)

    def matching(self, pattern: str) -> List[Op]:
        rx = re.compile(pattern)
        hit = {n for n in {o.name for o in self.ops} if rx.search(n)}
        return [o for o in self.ops if o.name in hit]

    def modules_matching(self, pattern: str) -> List[Op]:
        rx = re.compile(pattern)
        return [m for m in self.modules if rx.search(m.name)]


@dataclasses.dataclass
class DeviceTrace:
    devices: List[Device]
    host: List[Op]                      # harness annotations (bench.*)

    def host_offset(self, harness_steps: Sequence[Interval],
                    window: Interval) -> float:
        """Profile clock minus ``perf_counter``, from the harness's
        ``bench.step`` spans, which both clocks saw: the harness's steps
        that began in the profiled ``window`` (``perf_counter``) are lined
        up with the traced ones at the shift whose differences agree
        best (the profiler may miss a step at either end)."""
        prof = sorted(o.start for o in self.host if o.name == "bench.step")
        ours = sorted(s[0] for s in harness_steps
                      if window[0] <= s[0] <= window[1])
        if not prof or not ours:
            raise ValueError("no bench.step annotation in the profiled window")
        short, long_ = sorted((prof, ours), key=len)
        best = None
        for k in range(len(long_) - len(short) + 1):
            seg = long_[k:k + len(short)]
            d = sorted((p - o) for p, o in (zip(short, seg) if short is prof
                                            else zip(seg, short)))
            if best is None or d[-1] - d[0] < best[0]:
                best = (d[-1] - d[0], d[len(d) // 2])
        return best[1]


def load(log_dir: str) -> DeviceTrace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    op = Op(ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                    (mods if line.name == MODULES_LINE else ops).append(op)
            devices.append(Device(plane.name, ops, mods))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append(Op(ev.name, ev.start_ns * 1e-9,
                                       (ev.start_ns + ev.duration_ns)
                                       * 1e-9))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return DeviceTrace(devices, host)


# -- interval arithmetic ---------------------------------------------------
def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged: Iterable[Interval]) -> float:
    return sum(b - a for a, b in merged)


def clip(merged: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in merged if b > lo and a < hi]


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in clip(merged, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap_list: Iterable[Interval],
              spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds of idle device time per host activity: each gap goes to
    the innermost (shortest) span covering its midpoint."""
    out: Dict[str, float] = {}
    for a, b in gap_list:
        mid = 0.5 * (a + b)
        best: Optional[Tuple[str, float, float]] = None
        for s in spans:
            if s[1] <= mid <= s[2] and (best is None or
                                        s[2] - s[1] < best[2] - best[1]):
                best = s
        name = best[0] if best is not None else "no host span"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def leaves(ops: Iterable[Op]) -> List[Op]:
    """The ops that hold no other op (a ``while`` holds its body's ops
    on the same line)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start >= o.end:
            out.append(o)
    return out


_HLO = re.compile(r"^%\S+ = (.*?) ([a-z][\w-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def label(name: str) -> str:
    """An op's kind and output shape from its HLO text, so that the
    numbered instances of one op add up: ``copy s8[1,1345,32,36,64]``."""
    m = _HLO.match(name)
    if m is None:
        return name[:120]
    shape = _LAYOUT.sub("", _LAYOUT.sub("", m.group(1)))
    return f"{m.group(2)} {shape}"[:120]


def op_totals(ops: Iterable[Op]) -> Dict[str, float]:
    """Seconds per op label, over leaf ops."""
    out: Dict[str, float] = {}
    for o in leaves(ops):
        k = label(o.name)
        out[k] = out.get(k, 0.0) + o.dur
    return out
