"""Quantized paged-KV block format: int8 values + per-row float scales.

The serving arena's page pools are the decode hot loop's working set, and
decode is memory-bound — bytes/sec IS tokens/sec.  ``QuantPages`` packs a
KV pool as symmetric per-token-per-head int8 with f32 scales held beside
the values:

* every block-index operation the arena performs (COW copies, prefix-cache
  sharing, trash-block masking, block-table gathers) applies to values
  and scales together — the scales *travel with the blocks*;
* the paged attention kernels read int8 tiles plus one row of scales per
  page and apply the scales in-register, never materializing a float
  pool (``paged_pool`` gives the stored layout of both arrays);
* ``QuantPages`` is a registered pytree whose ``.shape``/``.dtype`` proxy
  the value array, so shape-reading call sites (scan carries, pjit
  shardings) treat it like the plain pool it replaces.

Quantization format (the one both the Pallas kernels and the jnp ref
reproduce bit-for-bit, since de/quantization is the same jnp math):

    scale = max(|x| over the last axis) / 127, floored at ``EPS``
    q     = round(clip(x / scale, -127, 127)) as int8
    x'    = float32(q) * scale
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INT8_MAX = 127.0
EPS = 1e-8          # zero rows quantize to zeros, never divide by zero


@jax.tree_util.register_pytree_node_class
class QuantPages:
    """An int8 array plus its per-row float32 scales.

    ``quantize`` gives ``values.shape == (*lead, D)`` and ``scales.shape
    == (*lead,)``, one scale per row of the quantized axis; an arena pool
    stores both in the ``paged_pool`` layout.  Shape/dtype attributes
    proxy the value array.
    """
    __slots__ = ("values", "scales")

    def __init__(self, values, scales):
        self.values = values
        self.scales = scales

    @property
    def shape(self):
        return self.values.shape

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def ndim(self):
        return self.values.ndim

    def tree_flatten(self):
        return (self.values, self.scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return (f"QuantPages(values={getattr(self.values, 'shape', None)},"
                f" scales={getattr(self.scales, 'shape', None)})")


def quantize(x):
    """Symmetric per-row int8: (values int8, scales f32) with
    ``scales.shape == x.shape[:-1]``."""
    xf = jnp.asarray(x, jnp.float32)
    scales = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / INT8_MAX, EPS)
    q = jnp.clip(jnp.round(xf / scales[..., None]), -INT8_MAX, INT8_MAX)
    return q.astype(jnp.int8), scales.astype(jnp.float32)


def dequantize(values, scales, dtype=jnp.float32):
    """Inverse of ``quantize`` (up to the rounding loss)."""
    out = values.astype(jnp.float32) * scales[..., None].astype(jnp.float32)
    return out.astype(dtype)
