"""Model step, chunked-prefill program (``models/`` and the kernels): the
least time a chunk call's model operations and compulsory bytes could take
at the chip's peaks, over its device time, in percent.  Source: the
profiled window's executions of the chunk program, and each chunk's start
and token count from the program tracer's ``prefill_chunk`` spans."""
from harness import layers


def read(rec):
    if layers.profiled(rec) is None:
        return None
    lo, hi, off = layers.profiled(rec)
    calls = rec.chunk_calls(lo - off, hi - off)
    t = layers.mean_time_per_run(rec, layers.CHUNK_PROGRAM)
    if not calls or t is None:
        return None
    work = [rec.family.chunk_step(rec.dims, s, n) for s, n in calls]
    ops = sum(w[0] for w in work) / len(work)
    byt = sum(w[1] for w in work) / len(work)
    return layers.share(rec, ops, byt, t)
