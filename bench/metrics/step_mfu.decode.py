"""Model step, fused decode program (``models/`` and the kernels): the
least time the step's model operations and compulsory bytes (weights once,
the cache read, new rows written) could take at the chip's peaks, over the
step's device time, in percent.  Source: the profiled window's executions
of the decode program, and the live requests' cache lengths at each
decode round from the program's tracer."""
from harness import layers


def read(rec):
    if layers.profiled(rec) is None:
        return None
    lo, hi, off = layers.profiled(rec)
    steps = [s for s in rec.decode_steps(lo - off, hi - off) if s]
    t = layers.mean_time_per_run(rec, layers.DECODE_PROGRAM)
    if not steps or t is None:
        return None
    work = [rec.family.decode_step(rec.dims, s) for s in steps]
    ops = sum(w[0] for w in work) / len(work)
    byt = sum(w[1] for w in work) / len(work)
    return layers.share(rec, ops, byt, t)
