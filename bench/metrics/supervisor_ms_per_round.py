"""Control plane (``serving/failover.py`` supervisor, ``core/`` handler and
ring sync): host milliseconds per round in the supervisor's own ``step``
span outside the engine's ``step`` spans, averaged over the window's
rounds.  Source: the program's ``control`` spans and engine ``step``
spans, with no harness span involved."""
from harness import spans


def read(rec):
    rounds = spans.spans(rec, "control", "control", ("step",))
    if not rounds:
        return None
    inside = spans.time_inside(
        rounds, spans.engine(rec, "engine", ("step",), window=False))
    return spans.mean_ms([b - a - e for (a, b), e in zip(rounds, inside)])
