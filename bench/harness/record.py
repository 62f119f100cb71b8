"""What one run recorded, and the arithmetic every metric shares.

A run's record joins three sources on one clock (``perf_counter``):
the harness's own spans (``Driver``: when each request was due and sent,
each ``supervisor.step``), the program's tracer events (passed in through
``ServiceRuntime(tracer=...)``), and, in a traced run, the reduced device
trace.  Per-token emission times come from the tracer: a request's first
token is its ``first_token`` instant, and every later token lands at the
end of an engine ``sample`` event that falls inside its ``decode`` span
(still open when the window closes, or closed at its eviction).
"""
from __future__ import annotations

import bisect
import dataclasses
from types import ModuleType
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

_COMPLETE, _INSTANT = "X", "i"


@dataclasses.dataclass
class Timeline:
    rid: int
    queued: Optional[Tuple[float, float]] = None
    first_token: Optional[float] = None
    decode_end: Optional[float] = None
    chunks: List[Tuple[float, float, int]] = dataclasses.field(
        default_factory=list)           # (start, end, tokens)
    emissions: List[float] = dataclasses.field(default_factory=list)


def percentile(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


@dataclasses.dataclass
class RunRecord:
    cell: str
    chips: int
    family: ModuleType                  # bench/families/<family>.py
    dims: Any                           # the family's record of sizes
    peaks: Dict
    slots: int
    window: Tuple[float, float]
    events: List[Tuple]                 # the program tracer's events
    svc: str
    reqs: Dict                          # rid -> driver ReqRecord
    steps: List[Tuple[float, float]]    # harness supervisor.step spans
    profile: Optional[object] = None    # devtrace.DeviceTrace
    profile_window: Optional[Tuple[float, float]] = None
    settled: float = 0.0                # when the harness stopped waiting

    def __post_init__(self):
        self.samples: List[Tuple[float, int]] = []     # (end, live)
        self.engine_steps: List[Tuple[float, float]] = []
        self.phases: List[Tuple[str, float, float]] = []
        tl: Dict[int, Timeline] = {}
        for kind, pid, tid, name, t0, t1, args in self.events:
            if pid != self.svc:
                continue
            if tid == "engine":
                if name == "sample":
                    self.samples.append((t1, int(args.get("live", 0))))
                elif name == "step":
                    self.engine_steps.append((t0, t1))
                else:
                    self.phases.append((name, t0, t1))
                continue
            try:
                rid = int(tid)
            except ValueError:
                continue                # n>1 fork lanes: not used here
            t = tl.setdefault(rid, Timeline(rid))
            if kind == _INSTANT and name == "first_token":
                t.first_token = t0
            elif name == "queued":
                t.queued = (t0, t1)
            elif name == "decode":
                t.decode_end = t1
            elif name == "prefill_chunk":
                t.chunks.append((t0, t1, int(args.get("tokens", 0))))
        self.samples.sort()
        ends = [s[0] for s in self.samples]
        for t in tl.values():
            if t.first_token is None:
                continue
            hi = float("inf") if t.decode_end is None else t.decode_end
            lo_i = bisect.bisect_right(ends, t.first_token)
            hi_i = bisect.bisect_right(ends, hi)
            t.emissions = [t.first_token] + ends[lo_i:hi_i]
        self.timelines = tl

    # -- windows ---------------------------------------------------------
    def in_window(self, t: float) -> bool:
        return self.window[0] <= t <= self.window[1]

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    # -- end to end ------------------------------------------------------
    def ttfts(self) -> List[float]:
        """First-token time less the time the request was due, for every
        request due in the window; one whose first token had not come when
        the harness stopped waiting counts the wait until then."""
        out = []
        for r, q in self.reqs.items():
            if not self.in_window(q.due):
                continue
            t = self.timelines.get(r)
            ft = t.first_token if t is not None else None
            out.append((ft if ft is not None
                        else max(self.settled, self.window[1])) - q.due)
        return out

    def itls(self) -> List[float]:
        """Every gap between consecutive output tokens of a request, for
        tokens emitted in the window."""
        out = []
        for t in self.timelines.values():
            e = t.emissions
            out.extend(b - a for a, b in zip(e, e[1:])
                       if self.in_window(b))
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for t in self.timelines.values() for x in t.emissions
                   if self.in_window(x))

    # -- model work ------------------------------------------------------
    def decode_steps(self, lo: float, hi: float) -> List[List[int]]:
        """For each engine sample (one fused decode step) ending in
        [lo, hi]: the key count each live request attended over."""
        out = []
        for end, _live in self.samples:
            if not lo <= end <= hi:
                continue
            lens = []
            for r, t in self.timelines.items():
                if r not in self.reqs or t.first_token is None:
                    continue
                if t.first_token < end and (t.decode_end is None
                                            or end <= t.decode_end):
                    j = bisect.bisect_right(t.emissions, end) - 1
                    lens.append(self.reqs[r].prompt_len + j)
            out.append(lens)
        return out

    def chunk_calls(self, lo: float, hi: float) -> List[Tuple[int, int]]:
        """(start, tokens) of every prefill chunk call ending in [lo, hi]."""
        out = []
        for t in self.timelines.values():
            start = 0
            for c0, c1, n in sorted(t.chunks):
                if lo <= c1 <= hi:
                    out.append((start, n))
                start += n
        return out

    def check_emissions(self, results) -> None:
        """Each finished request's emission count equals its tokens: the
        join of samples to decode spans is exact or the run is void."""
        for res in results:
            t = self.timelines.get(res.rid)
            if t is None or len(t.emissions) != len(res.tokens):
                raise RuntimeError(
                    f"request {res.rid}: {len(res.tokens)} tokens served but "
                    f"{0 if t is None else len(t.emissions)} emissions traced")
