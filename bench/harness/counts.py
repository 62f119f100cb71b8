"""Count arithmetic every family shares.

The numerators of every roofline and utilization share the benchmark
reports are a family's own counts (``bench/families/<family>.py``:
``decode_step``, ``chunk_step``, ``paged_decode_kernel``,
``paged_chunk_kernel``, each (operations, bytes) from shapes); here is
what they have in common: the keys of a causal chunk, and the share of a
roofline that a measured time gives.
"""
from __future__ import annotations

from typing import Tuple


def chunk_keys(start: int, n: int) -> int:
    """Keys attended by the ``n`` causal queries at positions
    ``start .. start + n - 1``."""
    return n * start + n * (n + 1) // 2


def roofline_share(ops: float, byt: float, seconds: float, peak_ops: float,
                   peak_bytes_per_s: float) -> Tuple[float, str]:
    """Least time the chip could take for (``ops``, ``byt``) over the time
    measured, in percent, and which of the two bounds it."""
    t_ops, t_mem = ops / peak_ops, byt / peak_bytes_per_s
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
