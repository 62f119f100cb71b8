"""Engine host work (``serving/engine.py``): host milliseconds per round,
the engine's ``step`` span less the device waits inside it, averaged over
the window's rounds.  The loop is synchronous, so the rest of a round is
waiting on the device.  Source: the program's engine ``step`` spans and
its ``wait`` spans."""
from harness import spans


def read(rec):
    waits = spans.engine(rec, "wait", window=False)
    if not waits:
        return None
    steps = spans.engine(rec, "engine", ("step",))
    inside = spans.time_inside(steps, waits)
    return spans.mean_ms([b - a - w for (a, b), w in zip(steps, inside)])
