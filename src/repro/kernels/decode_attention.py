"""Pallas TPU flash-decode kernels: one new token vs a long KV cache.

The decode hot loop is memory-bound (stream the whole cache once per token),
so the kernel's job is to keep the cache stream dense: grid = (batch*q_heads,
kv_blocks), kv sequential with (m, l, acc) carried in VMEM scratch — the
same online-softmax recurrence as prefill but with a single query row
broadcast across the sublane dimension.

Valid-length masking comes from a per-batch ``cache_len`` operand (int32,
one scalar per bh row) so ragged caches batch together; sliding windows
mask to the trailing ``window`` positions.

Two cache layouts are supported:

* **dense** — contiguous ``(B, S, Hkv, D)`` caches
  (``decode_attention_pallas``);
* **paged** — the serving arena's block-pool layout: physical pages
  ``(P, block_size, Hkv, D)`` plus a ``(B, blocks_per_slot)`` block table.
  ``paged_decode_attention_pallas`` scalar-prefetches the block table so
  each grid step's BlockSpec index map resolves logical block ``ki`` of
  batch ``b`` to its physical page — K/V stream straight from the pool
  with no gather materialization.  The model families' paged-native
  decode/chunk steps (``decode_step_paged`` / ``prefill_chunk_paged``)
  dispatch here through ``ops.paged_decode_attention`` /
  ``ops.paged_chunk_attention``; ``paged_gather_ref`` is the CPU/XLA
  fallback (per-slot gather through a ``mask_block_tables``-clipped
  table, then the dense kernel math).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF

DEFAULT_KV_BLOCK = 512
_SUB = 8  # sublane rows the single query is broadcast over


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, window: Optional[int],
                   k_block: int, nk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = len_ref[0, 0]
    k_lo = ki * k_block
    visible = k_lo < cache_len
    if window is not None:
        visible = jnp.logical_and(visible, k_lo + k_block > cache_len - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (_SUB, D) rows equal
        k = k_ref[0].astype(jnp.float32)            # (k_block, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (_SUB, k_block), 1)
        ok = kpos < cache_len
        if window is not None:
            ok = jnp.logical_and(ok, kpos >= cache_len - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1]) * ok.astype(jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, cache_len, *, window=None,
                            softmax_scale=None, k_block=DEFAULT_KV_BLOCK,
                            interpret=False):
    """q: (B, Hq, D); caches: (B, S, Hkv, D); cache_len: scalar or (B,) int.

    Returns (B, Hq, D).
    """
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len, jnp.int32)

    k_block = min(k_block, max(8, S))
    S_p = -(-S // k_block) * k_block
    kt = jnp.pad(k_cache, ((0, 0), (0, S_p - S), (0, 0), (0, 0)))
    vt = jnp.pad(v_cache, ((0, 0), (0, S_p - S), (0, 0), (0, 0)))
    kt = kt.transpose(0, 2, 1, 3).reshape(B * Hkv, S_p, D)
    vt = vt.transpose(0, 2, 1, 3).reshape(B * Hkv, S_p, D)
    # broadcast the single query over _SUB sublane rows
    qt = jnp.broadcast_to(q.reshape(B * Hq, 1, D), (B * Hq, _SUB, D))
    lens = jnp.repeat(cache_len, Hq).reshape(B * Hq, 1)

    nk = S_p // k_block
    grid = (B * Hq, nk)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               k_block=k_block, nk=nk)

    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, ki: (bh, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, _SUB, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, k_block, D),
                         lambda bh, ki, group=group: (bh // group, ki, 0)),
            pl.BlockSpec((1, k_block, D),
                         lambda bh, ki, group=group: (bh // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, _SUB, D), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, _SUB, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((_SUB, 128), jnp.float32),
            pltpu.VMEM((_SUB, 128), jnp.float32),
            pltpu.VMEM((_SUB, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qt, kt, vt)

    return out[:, 0].reshape(B, Hq, D)


# ---------------------------------------------------------------------------
# chunked prefill: a block of T query positions vs the (partial) cache
# ---------------------------------------------------------------------------

def _chunk_tile(start, end, ki, q, k, v, m_ref, l_ref, acc_ref,
                *, scale: float, prefix_len: int, k_block: int, Tp: int):
    """Shared online-softmax tile for the chunk-prefill kernels: query row
    i sits at absolute position ``start + i``; ``end`` = start + chunk_len
    bounds the valid cache (rows past chunk_len are padding and masked)."""
    q = q.astype(jnp.float32)                       # (Tp, D)
    k = k.astype(jnp.float32)                       # (k_block, D)
    v = v.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_lo = ki * k_block
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (Tp, k_block), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (Tp, k_block), 0)
    ok = kpos <= start + rows                       # causal over the cache
    if prefix_len:
        ok = jnp.logical_or(ok, kpos < prefix_len)  # bidirectional prefix
    ok = jnp.logical_and(ok, kpos < end)            # valid cache only
    ok = jnp.logical_and(ok, rows < end - start)    # padded q rows dead
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1]) * ok.astype(jnp.float32)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv


def _chunk_kernel(start_ref, end_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                  l_ref, acc_ref, *, scale: float, prefix_len: int,
                  k_block: int, nk: int, Tp: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start, end = start_ref[0, 0], end_ref[0, 0]

    @pl.when(ki * k_block < end)
    def _compute():
        _chunk_tile(start, end, ki, q_ref[0], k_ref[0], v_ref[0],
                    m_ref, l_ref, acc_ref, scale=scale,
                    prefix_len=prefix_len, k_block=k_block, Tp=Tp)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def chunk_prefill_attention_pallas(q, k_cache, v_cache, start, chunk_len, *,
                                   prefix_len: int = 0, softmax_scale=None,
                                   k_block=DEFAULT_KV_BLOCK,
                                   interpret=False):
    """q: (B, T, Hq, D) chunk queries; caches: (B, S, Hkv, D) already
    holding the chunk's own K/V at positions [start, start+chunk_len);
    start/chunk_len: scalar or (B,) int.  Returns (B, T, Hq, D); rows past
    ``chunk_len`` are zeros.
    """
    B, T, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.full((B,), start, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    if chunk_len.ndim == 0:
        chunk_len = jnp.full((B,), chunk_len, jnp.int32)

    Tp = -(-T // _SUB) * _SUB                       # sublane-align q rows
    k_block = min(k_block, max(8, S))
    S_p = -(-S // k_block) * k_block
    kt = jnp.pad(k_cache, ((0, 0), (0, S_p - S), (0, 0), (0, 0)))
    vt = jnp.pad(v_cache, ((0, 0), (0, S_p - S), (0, 0), (0, 0)))
    kt = kt.transpose(0, 2, 1, 3).reshape(B * Hkv, S_p, D)
    vt = vt.transpose(0, 2, 1, 3).reshape(B * Hkv, S_p, D)
    qt = q.transpose(0, 2, 1, 3)                    # (B, Hq, T, D)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    qt = qt.reshape(B * Hq, Tp, D)
    starts = jnp.repeat(start, Hq).reshape(B * Hq, 1)
    ends = jnp.repeat(start + chunk_len, Hq).reshape(B * Hq, 1)

    nk = S_p // k_block
    grid = (B * Hq, nk)
    kernel = functools.partial(_chunk_kernel, scale=scale,
                               prefix_len=prefix_len, k_block=k_block,
                               nk=nk, Tp=Tp)
    out = pl.pallas_call(
        kernel,
        name="chunk_prefill_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, ki: (bh, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda bh, ki: (bh, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Tp, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, k_block, D),
                         lambda bh, ki, group=group: (bh // group, ki, 0)),
            pl.BlockSpec((1, k_block, D),
                         lambda bh, ki, group=group: (bh // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, Tp, D), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Tp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Tp, 128), jnp.float32),
            pltpu.VMEM((Tp, 128), jnp.float32),
            pltpu.VMEM((Tp, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(starts, ends, qt, kt, vt)

    out = out.reshape(B, Hq, Tp, D)[:, :, :T]
    return out.transpose(0, 2, 1, 3)


def _paged_chunk_kernel(bt_ref, start_ref, end_ref, q_ref, k_ref, v_ref,
                        o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                        prefix_len: int, k_block: int, nk: int, Tp: int,
                        q_heads: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = start_ref[bh // q_heads]
    end = end_ref[bh // q_heads]

    # a logical block at or past the valid cache maps to the trash page
    @pl.when(ki * k_block < end)
    def _compute():
        _chunk_tile(start, end, ki, q_ref[0], k_ref[0, 0],
                    v_ref[0, 0], m_ref, l_ref, acc_ref, scale=scale,
                    prefix_len=prefix_len, k_block=k_block, Tp=Tp)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def paged_chunk_prefill_attention_pallas(q, k_pages, v_pages, block_tables,
                                         start, chunk_len, *,
                                         prefix_len: int = 0,
                                         softmax_scale=None,
                                         interpret=False):
    """Chunked-prefill attention straight through the serving arena's block
    table: q (B, T, Hq, D) chunk queries; pages (P, block_size, Hkv, D);
    block_tables (B, blocks_per_slot) int32; start/chunk_len (B,) int32.
    The chunk's own K/V must already be scattered into the pages (the
    engine writes pages before attending).  Returns (B, T, Hq, D).

    Like ``paged_decode_attention_pallas``, the table rides in scalar-
    prefetch SMEM so the K/V BlockSpec index maps stream physical pages in
    logical order; ``ops.paged_chunk_attention`` provides the dense-gather
    CPU fallback.

    This kernel is also the speculative-decoding VERIFY launch
    (``ops.paged_verify_attention``): T = k+1 rows score
    ``[last_emitted, d_1 .. d_k]`` in one call, with ``chunk_len`` a
    per-slot vector that is 0 for non-speculating rows of the fixed-
    capacity batch.  A zero-length row attends over an empty range — its
    softmax normalizer is 0 and the output row is garbage/NaN by design;
    the engine's verifier masks those rows and the row's K/V writes were
    routed to the trash page upstream.  No verify-specific kernel exists
    because the per-(B,) length plumbing below already expresses it.
    """
    B, T, Hq, D = q.shape
    P, k_block, Hkv, _ = k_pages.shape
    nk = block_tables.shape[1]
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.full((B,), start, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    if chunk_len.ndim == 0:
        chunk_len = jnp.full((B,), chunk_len, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)

    Tp = -(-T // _SUB) * _SUB
    kp = k_pages.transpose(2, 0, 1, 3)             # (Hkv, P, bs, D)
    vp = v_pages.transpose(2, 0, 1, 3)
    qt = q.transpose(0, 2, 1, 3)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    qt = qt.reshape(B * Hq, Tp, D)

    def kv_index(bh, ki, bt_ref, s_ref, e_ref):
        b = bh // Hq
        kvh = (bh % Hq) // group
        return (kvh, bt_ref[b, ki], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # table + start + end
        grid=(B * Hq, nk),
        in_specs=[
            pl.BlockSpec((1, Tp, D),
                         lambda bh, ki, bt, s, e: (bh, 0, 0)),
            pl.BlockSpec((1, 1, k_block, D), kv_index),
            pl.BlockSpec((1, 1, k_block, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, Tp, D),
                               lambda bh, ki, bt, s, e: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Tp, 128), jnp.float32),
            pltpu.VMEM((Tp, 128), jnp.float32),
            pltpu.VMEM((Tp, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_chunk_kernel, scale=scale,
                               prefix_len=prefix_len, k_block=k_block,
                               nk=nk, Tp=Tp, q_heads=Hq)
    out = pl.pallas_call(
        kernel,
        name="paged_chunk_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, Tp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, start, start + chunk_len, qt, kp, vp)

    out = out.reshape(B, Hq, Tp, D)[:, :, :T]
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# paged layout: K/V read through a block table (serving arena fast path)
# ---------------------------------------------------------------------------

def paged_gather_ref(pages, block_tables):
    """Dense-gather fallback: pages (P, bs, *rest) + tables (B, nblk)
    -> contiguous (B, nblk*bs, *rest).  ``rest`` is (Hkv, D) for value
    pools and (Hkv,) for the int8 pools' scale siblings.  Unallocated
    table entries point at the pool's trash block; callers mask them via
    ``cache_len``."""
    B, nblk = block_tables.shape
    _, bs, *rest = pages.shape
    g = pages[block_tables]                    # (B, nblk, bs, *rest)
    return g.reshape(B, nblk * bs, *rest)


def mask_block_tables(block_tables, valid_len, block_size, trash):
    """Route every table entry wholly past ``valid_len`` to the ``trash``
    block before a ref-fallback gather.

    The Pallas kernels skip blocks at or past each slot's valid length via
    their ``@pl.when`` gates, so their HBM traffic scales with LIVE tokens.
    The CPU/XLA gather cannot shrink its (static) output, but it can stop
    streaming cold pages the softmax will mask anyway: with every
    past-``valid_len`` entry pointing at the one trash page, the gather
    reads per-slot up-to-len rows plus a single hot page instead of the
    slot's full pool — bit-identical outputs (masked positions never
    survive the softmax) with live-token-bound unique-byte traffic."""
    nblk = block_tables.shape[1]
    starts = jnp.arange(nblk, dtype=jnp.int32)[None] * block_size
    valid_len = jnp.asarray(valid_len, jnp.int32)
    if valid_len.ndim == 0:
        valid_len = jnp.full((block_tables.shape[0],), valid_len)
    return jnp.where(starts < valid_len[:, None], block_tables, trash)


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float,
                         k_block: int, nk: int, q_heads: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = len_ref[bh // q_heads]
    k_lo = ki * k_block
    # a logical block past cache_len maps to the trash page: skip it
    @pl.when(k_lo < cache_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)           # (_SUB, D)
        k = k_ref[0, 0].astype(jnp.float32)        # (k_block, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (_SUB, k_block), 1)
        ok = kpos < cache_len
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1]) * ok.astype(jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pages, v_pages, block_tables,
                                  cache_len, *, softmax_scale=None,
                                  interpret=False):
    """q: (B, Hq, D); pages: (P, block_size, Hkv, D); block_tables:
    (B, blocks_per_slot) int32; cache_len: (B,) int32.  Returns (B, Hq, D).

    The block table rides in scalar-prefetch SMEM so the K/V BlockSpec
    index maps dereference it — the kernel streams physical pages in
    logical order without ever building the contiguous view.
    """
    B, Hq, D = q.shape
    P, k_block, Hkv, _ = k_pages.shape
    nk = block_tables.shape[1]
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)

    # per-kv-head page pools so one (head, physical block) pair is a tile
    kp = k_pages.transpose(2, 0, 1, 3)             # (Hkv, P, bs, D)
    vp = v_pages.transpose(2, 0, 1, 3)
    qt = jnp.broadcast_to(q.reshape(B * Hq, 1, D), (B * Hq, _SUB, D))

    def kv_index(bh, ki, bt_ref, len_ref):
        b = bh // Hq
        kvh = (bh % Hq) // group
        return (kvh, bt_ref[b, ki], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # block table + lens
        grid=(B * Hq, nk),
        in_specs=[
            pl.BlockSpec((1, _SUB, D), lambda bh, ki, bt, ln: (bh, 0, 0)),
            pl.BlockSpec((1, 1, k_block, D), kv_index),
            pl.BlockSpec((1, 1, k_block, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, _SUB, D), lambda bh, ki, bt, ln:
                               (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_SUB, 128), jnp.float32),
            pltpu.VMEM((_SUB, 128), jnp.float32),
            pltpu.VMEM((_SUB, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               k_block=k_block, nk=nk, q_heads=Hq)
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, _SUB, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, cache_len, qt, kp, vp)

    return out[:, 0].reshape(B, Hq, D)


# ---------------------------------------------------------------------------
# quantized paged layout: int8 page tiles + scalar-prefetched scale columns,
# dequantized in-register before QK/PV (the pool never exists in float)
# ---------------------------------------------------------------------------

def _quant_scale_pool(scales):
    """(P, bs, Hkv) f32 scale pool -> (Hkv, P, bs, 1): same per-kv-head
    physical-page tiling as the value pools, with a lane-dim singleton so
    the (k_block, 1) scale column broadcasts against (k_block, D) tiles."""
    return scales.transpose(2, 0, 1)[..., None]


def _paged_decode_kernel_quant(bt_ref, len_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, o_ref, m_ref, l_ref,
                               acc_ref, *, scale: float, k_block: int,
                               nk: int, q_heads: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = len_ref[bh // q_heads]
    k_lo = ki * k_block
    # a logical block past cache_len maps to the trash page: skip it
    @pl.when(k_lo < cache_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)           # (_SUB, D)
        # dequantize in-register: int8 tile * per-row scale column
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]  # (k_block, D)
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (_SUB, k_block), 1)
        ok = kpos < cache_len
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1]) * ok.astype(jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def paged_decode_attention_quant_pallas(q, k_pages, v_pages, k_scales,
                                        v_scales, block_tables, cache_len,
                                        *, softmax_scale=None,
                                        interpret=False):
    """Quantized sibling of ``paged_decode_attention_pallas``: pages are
    int8 (P, block_size, Hkv, D) with f32 scales (P, block_size, Hkv); the
    kernel streams int8 tiles + scale columns through the block table and
    dequantizes in-register — HBM decode traffic is 1 byte per KV element
    plus 4/D bytes of scale.
    """
    B, Hq, D = q.shape
    P, k_block, Hkv, _ = k_pages.shape
    nk = block_tables.shape[1]
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)

    kp = k_pages.transpose(2, 0, 1, 3)             # (Hkv, P, bs, D) int8
    vp = v_pages.transpose(2, 0, 1, 3)
    ks = _quant_scale_pool(k_scales)               # (Hkv, P, bs, 1) f32
    vs = _quant_scale_pool(v_scales)
    qt = jnp.broadcast_to(q.reshape(B * Hq, 1, D), (B * Hq, _SUB, D))

    def kv_index(bh, ki, bt_ref, len_ref):
        b = bh // Hq
        kvh = (bh % Hq) // group
        return (kvh, bt_ref[b, ki], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # block table + lens
        grid=(B * Hq, nk),
        in_specs=[
            pl.BlockSpec((1, _SUB, D), lambda bh, ki, bt, ln: (bh, 0, 0)),
            pl.BlockSpec((1, 1, k_block, D), kv_index),
            pl.BlockSpec((1, 1, k_block, D), kv_index),
            pl.BlockSpec((1, 1, k_block, 1), kv_index),
            pl.BlockSpec((1, 1, k_block, 1), kv_index),
        ],
        out_specs=pl.BlockSpec((1, _SUB, D), lambda bh, ki, bt, ln:
                               (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_SUB, 128), jnp.float32),
            pltpu.VMEM((_SUB, 128), jnp.float32),
            pltpu.VMEM((_SUB, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel_quant, scale=scale,
                               k_block=k_block, nk=nk, q_heads=Hq)
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention_int8",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, _SUB, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, cache_len, qt, kp, vp, ks, vs)

    return out[:, 0].reshape(B, Hq, D)


def _paged_chunk_kernel_quant(bt_ref, start_ref, end_ref, q_ref, k_ref,
                              v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref,
                              acc_ref, *, scale: float, prefix_len: int,
                              k_block: int, nk: int, Tp: int, q_heads: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = start_ref[bh // q_heads]
    end = end_ref[bh // q_heads]

    # a logical block at or past the valid cache maps to the trash page
    @pl.when(ki * k_block < end)
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        _chunk_tile(start, end, ki, q_ref[0], k, v, m_ref, l_ref,
                    acc_ref, scale=scale, prefix_len=prefix_len,
                    k_block=k_block, Tp=Tp)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def paged_chunk_prefill_attention_quant_pallas(q, k_pages, v_pages,
                                               k_scales, v_scales,
                                               block_tables, start,
                                               chunk_len, *,
                                               prefix_len: int = 0,
                                               softmax_scale=None,
                                               interpret=False):
    """Quantized sibling of ``paged_chunk_prefill_attention_pallas``: the
    chunk's own rows must already be *quantized* into the int8 pages (the
    write path quantizes before attending), so the kernel's dequantized
    view is exactly what decode will later read."""
    B, T, Hq, D = q.shape
    P, k_block, Hkv, _ = k_pages.shape
    nk = block_tables.shape[1]
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.full((B,), start, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    if chunk_len.ndim == 0:
        chunk_len = jnp.full((B,), chunk_len, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)

    Tp = -(-T // _SUB) * _SUB
    kp = k_pages.transpose(2, 0, 1, 3)             # (Hkv, P, bs, D) int8
    vp = v_pages.transpose(2, 0, 1, 3)
    ks = _quant_scale_pool(k_scales)               # (Hkv, P, bs, 1) f32
    vs = _quant_scale_pool(v_scales)
    qt = q.transpose(0, 2, 1, 3)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    qt = qt.reshape(B * Hq, Tp, D)

    def kv_index(bh, ki, bt_ref, s_ref, e_ref):
        b = bh // Hq
        kvh = (bh % Hq) // group
        return (kvh, bt_ref[b, ki], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # table + start + end
        grid=(B * Hq, nk),
        in_specs=[
            pl.BlockSpec((1, Tp, D),
                         lambda bh, ki, bt, s, e: (bh, 0, 0)),
            pl.BlockSpec((1, 1, k_block, D), kv_index),
            pl.BlockSpec((1, 1, k_block, D), kv_index),
            pl.BlockSpec((1, 1, k_block, 1), kv_index),
            pl.BlockSpec((1, 1, k_block, 1), kv_index),
        ],
        out_specs=pl.BlockSpec((1, Tp, D),
                               lambda bh, ki, bt, s, e: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Tp, 128), jnp.float32),
            pltpu.VMEM((Tp, 128), jnp.float32),
            pltpu.VMEM((Tp, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_chunk_kernel_quant, scale=scale,
                               prefix_len=prefix_len, k_block=k_block,
                               nk=nk, Tp=Tp, q_heads=Hq)
    out = pl.pallas_call(
        kernel,
        name="paged_chunk_attention_int8",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, Tp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, start, start + chunk_len, qt, kp, vp, ks, vs)

    out = out.reshape(B, Hq, Tp, D)[:, :, :T]
    return out.transpose(0, 2, 1, 3)
