"""Control plane (``core/`` handler and ring sync, ``serving/failover.py``
supervisor): host milliseconds per round spent in ``supervisor.step``
outside the engine's own ``step`` spans, averaged over the window's
rounds.  Source: the harness's span around each ``supervisor.step`` and
the program's engine ``step`` spans inside it."""
import bisect


def read(rec):
    eng = sorted(rec.engine_steps)
    starts = [a for a, _ in eng]
    own = []
    for a, b in rec.steps:
        if not rec.in_window(a):
            continue
        i = bisect.bisect_left(starts, a)
        inside = 0.0
        while i < len(eng) and eng[i][1] <= b:
            inside += eng[i][1] - eng[i][0]
            i += 1
        own.append(b - a - inside)
    return 1e3 * sum(own) / len(own) if own else None
