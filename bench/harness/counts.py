"""Operations and bytes that the algorithm needs, computed from shapes.

These are the numerators of every roofline and utilization share the
benchmark reports.  They count what the mathematics requires, never what
an implementation happens to move: a kernel that reads more than this (a
transposed copy of a whole pool, padded rows, idle slots) shows as a lower
share, not as a larger count.  Under tensor parallelism over ``chips``
devices every matrix and every head is split evenly, so each device's
share of a count is the count divided by ``chips``.

Conventions: one multiply-add is 2 operations; a cache length ``n`` counts
the keys a query attends over, the new token's own key included.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    kv_dtype: str              # "bf16" or "int8"
    weight_bytes: int = 2      # bf16 weights and activations

    @property
    def layer_matmul_params(self) -> int:
        """Weights of one layer's matrix multiplications (q, k, v, o and
        the gated MLP's gate, up and down)."""
        d, a, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * a + 2 * d * kv + a * d + 3 * d * self.d_ff

    @property
    def layer_params(self) -> int:
        """Every weight of one layer: matrices, two norms, q/k/v biases."""
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim \
            if self.qkv_bias else 0
        return self.layer_matmul_params + 2 * self.d_model + bias

    @property
    def weights_read_bytes(self) -> int:
        """Bytes of weights one forward step must read once: every layer,
        the final norm and the output head (the embedding table itself
        when tied)."""
        return self.weight_bytes * (self.layers * self.layer_params
                                    + self.d_model
                                    + self.d_model * self.vocab)

    @property
    def kv_token_bytes(self) -> int:
        """Cache bytes of one token in one layer: K and V, plus the int8
        pools' per-row f32 scales."""
        e = 1 if self.kv_dtype == "int8" else self.weight_bytes
        per = 2 * self.kv_heads * self.head_dim * e
        if self.kv_dtype == "int8":
            per += 2 * self.kv_heads * 4
        return per

    def attn_ops(self, n_keys: int) -> int:
        """QK^T and PV of one query over ``n_keys`` keys, every layer."""
        return 4 * self.layers * self.heads * self.head_dim * n_keys

    @property
    def token_matmul_ops(self) -> int:
        """Operations of one token through every layer's matrices and the
        output head (attention over the cache excluded)."""
        return 2 * (self.layers * self.layer_matmul_params
                    + self.d_model * self.vocab)


def model_ops_per_token(dims: Dims, n_keys: int) -> int:
    """Model operations of one token that attends over ``n_keys`` keys."""
    return dims.token_matmul_ops + dims.attn_ops(n_keys)


def decode_step(dims: Dims, lens: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of one fused decode step in which each live
    slot attends over ``lens[b]`` keys (its own new key included): the
    matrices once per live slot, the weights read once, each slot's
    earlier cache rows read and its new row written."""
    lens = list(lens)
    ops = sum(model_ops_per_token(dims, n) for n in lens)
    byt = (dims.weights_read_bytes
           + dims.layers * dims.kv_token_bytes * sum(n - 1 for n in lens)
           + dims.layers * dims.kv_token_bytes * len(lens)
           + dims.weight_bytes * dims.d_model * len(lens))   # embed rows
    return float(ops), float(byt)


def paged_decode_kernel(dims: Dims, lens: Iterable[int]) -> Tuple[float,
                                                                  float]:
    """(operations, bytes) of the paged decode attention kernel over every
    layer of one step: each live slot's query attends over its ``n`` keys,
    read from the pool; the query comes in and the output goes out."""
    lens = list(lens)
    ops = sum(dims.attn_ops(n) for n in lens)
    qo = 2 * dims.heads * dims.head_dim * dims.weight_bytes
    byt = dims.layers * (dims.kv_token_bytes * sum(lens) + qo * len(lens))
    return float(ops), float(byt)


def _chunk_keys(start: int, n: int) -> int:
    """Keys attended by the ``n`` causal queries at positions
    ``start .. start + n - 1``."""
    return n * start + n * (n + 1) // 2


def chunk_step(dims: Dims, start: int, n: int) -> Tuple[float, float]:
    """(operations, bytes) of one chunked-prefill call that adds ``n``
    prompt tokens after ``start`` cached ones and yields one row of
    logits: the matrices for ``n`` tokens, causal attention, the weights
    read once, the earlier rows read and the new rows written."""
    ops = (2 * n * dims.layers * dims.layer_matmul_params
           + 2 * dims.d_model * dims.vocab
           + 4 * dims.layers * dims.heads * dims.head_dim
           * _chunk_keys(start, n))
    byt = (dims.weights_read_bytes
           + dims.layers * dims.kv_token_bytes * (start + n)
           + dims.weight_bytes * dims.d_model * n)
    return float(ops), float(byt)


def paged_chunk_kernel(dims: Dims, start: int, n: int) -> Tuple[float,
                                                                float]:
    """(operations, bytes) of the paged chunk attention kernel over every
    layer of one call: ``n`` causal queries after ``start`` cached keys,
    with the chunk's own rows already in the pool."""
    ops = 4 * dims.layers * dims.heads * dims.head_dim * _chunk_keys(start, n)
    qo = 2 * n * dims.heads * dims.head_dim * dims.weight_bytes
    byt = dims.layers * (dims.kv_token_bytes * (start + n) + qo)
    return float(ops), float(byt)


def roofline_share(ops: float, byt: float, seconds: float, peak_ops: float,
                   peak_bytes_per_s: float) -> Tuple[float, str]:
    """Least time the chip could take for (``ops``, ``byt``) over the time
    measured, in percent, and which of the two bounds it."""
    t_ops, t_mem = ops / peak_ops, byt / peak_bytes_per_s
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
