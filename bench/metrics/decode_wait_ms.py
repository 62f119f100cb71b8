"""Model step, fused decode: milliseconds the host waits for each fused
decode step's sampled tokens, the decode program and the sampler together,
averaged over the window's decode calls.  Source: the program's
``wait`` ``decode`` spans."""
from harness import spans


def read(rec):
    return spans.mean_ms([b - a for a, b in
                          spans.engine(rec, "wait", ("decode",))])
