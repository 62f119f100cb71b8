"""Kernels (``kernels/decode_attention.py``, the paged decode attention
kernel, int8 or bf16 as the cell runs): operations and bytes the algorithm
needs per decode step, from the live requests' cache lengths, over the
kernel's device time per step, in percent.  Under ``shard_map`` each
device runs its share of the heads."""
from harness import layers


def read(rec):
    if layers.profiled(rec) is None:
        return None
    lo, hi, off = layers.profiled(rec)
    steps = [s for s in rec.decode_steps(lo - off, hi - off) if s]
    t = layers.mean_time_per_run(rec, layers.DECODE_PROGRAM,
                                 layers.DECODE_KERNEL)
    if not steps or t is None:
        return None
    work = [rec.family.paged_decode_kernel(rec.dims, s) for s in steps]
    ops = sum(w[0] for w in work) / len(work)
    byt = sum(w[1] for w in work) / len(work)
    return layers.share(rec, ops, byt, t)
