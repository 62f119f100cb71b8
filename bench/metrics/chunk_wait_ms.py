"""Model step, chunked prefill: milliseconds the host waits for each
chunk call (a non-final chunk's logits, or a final chunk's first token),
averaged over the window's chunk calls.  Source: the program's ``wait``
``chunk`` and ``first_token`` spans."""
from harness import spans


def read(rec):
    return spans.mean_ms([b - a for a, b in spans.engine(
        rec, "wait", ("chunk", "first_token"))])
