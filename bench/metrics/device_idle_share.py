"""Device: the share of the profiled window in which no operation runs on
the device, averaged over the cell's devices, in percent.  Source: the
union of the ``XLA Ops`` intervals of the profiler trace."""
from harness import devtrace, layers


def read(rec):
    if layers.profiled(rec) is None:
        return None
    lo, hi, _ = layers.profiled(rec)
    busy = [devtrace.length(devtrace.clip(
        devtrace.merge((o.start, o.end) for o in d.ops), lo, hi))
        for d in rec.profile.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
