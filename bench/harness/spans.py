"""The program's own round spans, read from its tracer's ring events.

The engine records each scheduling round as an ``engine`` ``step`` span,
and each call where the host blocks on the device as a span on its
``wait`` timeline; the cluster supervisor records its round as a
``control`` ``step`` span.  These are read over the whole window, not
only its profiled part.  A program that records none of them gives empty
lists, and the readers built on them read nothing.
"""
from __future__ import annotations

import bisect
from typing import List, Sequence, Tuple

Interval = Tuple[float, float]


def spans(rec, pid: str, tid: str, names: Sequence[str] = (),
          window: bool = True) -> List[Interval]:
    """(start, end) of the complete events on the ``pid``/``tid`` timeline
    (only ``names`` where given), sorted; with ``window``, only those that
    start in the window (a round that starts in it is counted whole)."""
    return sorted((t0, t1) for kind, p, t, name, t0, t1, _ in rec.events
                  if kind == "X" and p == pid and t == tid
                  and (not names or name in names)
                  and (not window or rec.in_window(t0)))


def engine(rec, tid: str, names: Sequence[str] = (),
           window: bool = True) -> List[Interval]:
    return spans(rec, rec.svc, tid, names, window)


def time_inside(outer: Sequence[Interval],
                inner: Sequence[Interval]) -> List[float]:
    """For each of ``outer``: the seconds of the sorted, non-overlapping
    ``inner`` spans that lie within it."""
    starts = [a for a, _ in inner]
    out = []
    for a, b in outer:
        i = bisect.bisect_left(starts, a)
        t = 0.0
        while i < len(inner) and inner[i][1] <= b:
            t += inner[i][1] - inner[i][0]
            i += 1
        out.append(t)
    return out


def mean_ms(xs: Sequence[float]):
    return 1e3 * sum(xs) / len(xs) if xs else None
