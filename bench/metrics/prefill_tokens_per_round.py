"""Engine prefill scheduling (``serving/engine.py``, chunked prefill):
prompt tokens prefilled per round, over the window's rounds that ran a
chunk call.  The per-round chunk budget (``prefill_chunk``) caps it; a
first token waits about prompt length over this many rounds.  Source: the
program's ``prefill_chunk`` spans (their ``tokens``) and engine ``step``
spans."""
import bisect

from harness import spans


def read(rec):
    steps = spans.engine(rec, "engine", ("step",))
    starts = [a for a, _ in steps]
    per_round = {}
    for t in rec.timelines.values():
        for c0, _, n in t.chunks:
            i = bisect.bisect_right(starts, c0) - 1
            if i >= 0 and c0 <= steps[i][1]:
                per_round[i] = per_round.get(i, 0) + n
    return sum(per_round.values()) / len(per_round) if per_round else None
