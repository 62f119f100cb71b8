"""Kernels (``kernels/decode_attention.py``, the paged chunk-prefill
attention kernel): operations and bytes the algorithm needs per chunk
call, from each chunk's start and length, over the kernel's device time
per call, in percent."""
from harness import layers


def read(rec):
    if layers.profiled(rec) is None:
        return None
    lo, hi, off = layers.profiled(rec)
    calls = rec.chunk_calls(lo - off, hi - off)
    t = layers.mean_time_per_run(rec, layers.CHUNK_PROGRAM,
                                 layers.CHUNK_KERNEL)
    if not calls or t is None:
        return None
    work = [rec.family.paged_chunk_kernel(rec.dims, s, n) for s, n in calls]
    ops = sum(w[0] for w in work) / len(work)
    byt = sum(w[1] for w in work) / len(work)
    return layers.share(rec, ops, byt, t)
