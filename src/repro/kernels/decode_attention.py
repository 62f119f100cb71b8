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
* **paged** — the serving arena's stacked block pools, stored in the
  layout these kernels read (``paged_pool``): values ``(layers, Hkv/G,
  P, block_size, W)`` with ``G`` KV heads side by side in a row of whole
  128-lane tiles, int8 scales in unpadded rows, plus a ``(B,
  blocks_per_slot)`` block table.  One kernel serves chunked prefill,
  verify and decode (a one-row chunk): it scalar-prefetches the layer
  index and the block table so each grid step's BlockSpec index map
  resolves logical block ``ki`` of batch ``b`` to its ``(layer, head
  group, physical page)`` tile — K/V stream straight from the stored
  pool with no slice, relayout or gather.  A grid step scores every
  query head of one head group at every chunk position at once: each
  query row sits in its own KV head's lanes of a lane-masked tile, and
  the output keeps each row's own lane block.  The model families'
  paged-native decode/chunk steps (``decode_step_paged`` /
  ``prefill_chunk_paged``) dispatch here through
  ``ops.paged_decode_attention`` / ``ops.paged_chunk_attention``;
  ``paged_pool.gather`` is the CPU/XLA fallback (per-slot gather through
  a ``mask_block_tables``-clipped table, then the dense kernel math).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_pool
from .ref import NEG_INF

DEFAULT_KV_BLOCK = 512
_LANES, _SUBLANES = paged_pool.LANES, paged_pool.SUBLANES
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
    """Online-softmax tile of the dense chunk-prefill kernel: query row
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


# ---------------------------------------------------------------------------
# paged layout: K/V read through a block table straight from the arena's
# stacked pools (``paged_pool``), int8 pools dequantized in-register
# ---------------------------------------------------------------------------

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


def _head_scale(row, off, block_size: int):
    """One head's scales of one page, ``(1, block_size)``, out of a scale
    row ``(1, W)`` whose lanes ``[off, off + block_size)`` hold them
    (``off`` a multiple of ``block_size``, ``W / block_size`` a power of
    two).  The segment is masked, then the row folds onto its first
    ``block_size`` lanes: each cyclic rotation by half the width adds the
    lanes congruent modulo it, whichever way the rotation turns."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    x = jnp.where((lane >= off) & (lane < off + block_size), row, 0.0)
    w = row.shape[-1]
    while w > block_size:
        w //= 2
        x = x + pltpu.roll(x, w, 1)
    return x[:, :block_size]


def _spread_heads(x, group: int, axis: int):
    """``x`` (..., G at ``axis``, ..., D) -> (..., G*D): head ``h`` of its
    group keeps its values in lanes ``[h*D, (h+1)*D)`` and zeros
    elsewhere, so one dot against a ``(block, W)`` tile scores each query
    against its own KV head only."""
    G, D = group, x.shape[-1]
    eye = jnp.eye(G, dtype=bool).reshape(
        (G,) + (1,) * (x.ndim - 2 - axis) + (G, 1))
    out = jnp.where(eye, x[..., None, :], jnp.zeros((), x.dtype))
    return out.reshape(*out.shape[:-2], G * D)


# query rows x lanes of one grid step's tile: bounds the kernel's VMEM
# (query and output tiles, the f32 accumulator, the softmax rows) well
# inside the default scoped limit
_TILE_ELEMS = 1 << 18


def _heads_per_tile(J: int, Tp: int, lanes: int) -> int:
    """The most query heads of a head group whose ``Tp`` rows each share
    one tile: the largest divisor of ``J`` within ``_TILE_ELEMS``."""
    return max(d for d in range(1, J + 1)
               if J % d == 0 and (d == 1 or d * Tp * lanes <= _TILE_ELEMS))


def _log2(n: int) -> int:
    """Shift for a power of two: page arithmetic is shifts and masks, as
    the scalar unit runs it on every grid step."""
    k = n.bit_length() - 1
    assert n == 1 << k, n
    return k


def _scale_row0(page, per_row: int, page_rows: int):
    """A page's first row in its scale pool."""
    return (page >> _log2(per_row)) << _log2(page_rows)


def _paged_kernel(layer_ref, bt_ref, start_ref, end_ref, q_ref, k_ref, v_ref,
                  *refs, scale: float, prefix_len: int, k_block: int,
                  group: int, q_per_kv: int, per_row: int, page_rows: int,
                  Tp: int, jb: int, split: bool, nk: int, quant: bool):
    """One (slot, head group, [row block,] page) grid step: the row
    block's query rows (``jb`` query heads of the group x ``Tp``
    positions, each in its own KV head's lanes) against one page's
    ``(k_block, W)`` tile, an online softmax over the pages.  The grid
    has a row-block axis only where a group's rows ``split``."""
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    rb = pl.program_id(2) if split else 0
    ki = pl.program_id(3 if split else 2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = start_ref[b]
    end = end_ref[b]

    # a logical block at or past the valid cache maps to the trash page
    @pl.when(ki * k_block < end)
    def _compute():
        q = q_ref[...].astype(jnp.float32)         # (R, W) lane-masked
        k = k_ref[...].astype(jnp.float32)         # (k_block, W)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        R = s.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, (R, k_block), 0)
        # row r is query head jj = r // Tp of the block at position
        # t = r % Tp of the chunk
        if Tp == 1:
            jj, t = rows, 0
        else:
            jj = jnp.zeros_like(rows)
            for n in range(1, jb):
                jj = jj + (rows >= n * Tp).astype(jnp.int32)
            t = rows - jj * Tp
        if quant:
            # int8 tiles: the per-token scales multiply the scores (K) and
            # the probabilities (V), each row by its own head's scales
            j = rb * jb + jj
            page = bt_ref[b, ki]
            first = _scale_row0(page, per_row, page_rows)
            base = 0 if per_row == 1 else (
                (page & (per_row - 1)) * (group * k_block))
            ks = jnp.zeros((R, k_block), jnp.float32)
            vs = jnp.zeros((R, k_block), jnp.float32)
            for h in range(group):
                mine = (j >= h * q_per_kv) & (j < (h + 1) * q_per_kv)
                flat = base + h * k_block
                # the head's row of the (8, 128) scale block
                row = pl.ds((first + (flat >> _log2(_LANES)))
                            & (_SUBLANES - 1), 1)
                off = flat & (_LANES - 1)
                ks = jnp.where(mine, _head_scale(ks_ref[row, :], off,
                                                 k_block), ks)
                vs = jnp.where(mine, _head_scale(vs_ref[row, :], off,
                                                 k_block), vs)
            s = s * ks
        kpos = ki * k_block + jax.lax.broadcasted_iota(jnp.int32,
                                                       (R, k_block), 1)
        if Tp == 1:
            # one row a head: the valid cache bounds it (a decode step)
            ok = jnp.logical_and(kpos < end, start < end)
        else:
            ok = kpos <= start + t                 # causal over the cache
            if prefix_len:
                ok = jnp.logical_or(ok, kpos < prefix_len)  # bidirectional
            ok = jnp.logical_and(ok, kpos < end)   # valid cache only
            ok = jnp.logical_and(ok, t < end - start)  # padded q rows dead
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1]) * ok.astype(jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        if quant:
            p = p * vs
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(
            o_ref.dtype)


def _paged_attention(q, k_pool, v_pool, scales, block_tables, start,
                     chunk_len, *, layer, kv_heads, prefix_len,
                     softmax_scale, interpret, name):
    """q (B, T, Hq, D) at positions ``start + i`` against the stored pools
    (see ``paged_chunk_prefill_attention_pallas``); decode is T = 1."""
    B, T, Hq, D = q.shape
    geo = paged_pool.geometry(k_pool, None if scales is None
                              else scales[0], D, kv_heads)
    G, W, k_block = geo.group, geo.lanes, geo.block_size
    Hg = k_pool.shape[1]
    if Hq % (Hg * G):
        raise ValueError(f"{Hq} query heads do not share {Hg * G} KV heads")
    q_per_kv = Hq // (Hg * G)
    J = G * q_per_kv                               # query heads a group
    Tp = 1 if T == 1 else -(-T // _SUB) * _SUB
    jb = _heads_per_tile(J, Tp, W)
    R = -(-jb * Tp // _SUB) * _SUB                 # whole sublanes
    nk = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    end = start + jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (B,))
    tables = jnp.asarray(block_tables, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    # one grid row per (slot, head group, row block): the block's query
    # heads' Tp rows stacked head-major in one lane-masked tile, so each
    # K/V tile is read once per (slot, head group, row block, page)
    qt = jnp.pad(q.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    qt = _spread_heads(qt.reshape(B, Hg, G, q_per_kv, Tp, D), G, 2)
    qt = qt.reshape(B, Hg, J // jb, jb * Tp, G * D)
    qt = jnp.pad(qt, ((0, 0),) * 3 + ((0, R - jb * Tp), (0, W - G * D)))

    split = J // jb > 1

    def ids(args):
        """``(b, g, rb, ki, prefetch refs)`` of an index map's arguments."""
        b, g, *rest = args
        if split:
            rb, ki, *refs = rest
            return b, g, rb, ki, refs
        ki, *refs = rest
        return b, g, 0, ki, refs

    def kv_index(*args):
        b, g, _, ki, (layer_ref, bt_ref, *_) = ids(args)
        return (layer_ref[0], g, bt_ref[b, ki], 0, 0)

    def scale_index(*args):
        b, g, _, ki, (layer_ref, bt_ref, *_) = ids(args)
        first = _scale_row0(bt_ref[b, ki], geo.pages_per_row,
                            geo.rows_per_page)
        return (layer_ref[0], g, first >> _log2(_SUBLANES), 0)

    def row_index(*args):
        b, g, rb, _, _ = ids(args)
        return (b, g, rb, 0, 0)

    in_specs = [pl.BlockSpec((None, None, None, R, W), row_index),
                pl.BlockSpec((None, None, None, k_block, W), kv_index),
                pl.BlockSpec((None, None, None, k_block, W), kv_index)]
    operands = [qt, k_pool, v_pool]
    if scales is not None:
        in_specs += [pl.BlockSpec((None, None, _SUBLANES, _LANES),
                                  scale_index)] * 2
        operands += list(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                     # layer, table, start, end
        grid=(B, Hg, J // jb, nk) if split else (B, Hg, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, None, R, W), row_index),
        scratch_shapes=[
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, W), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale,
                               prefix_len=prefix_len, k_block=k_block,
                               group=G, q_per_kv=q_per_kv,
                               per_row=geo.pages_per_row,
                               page_rows=geo.rows_per_page, Tp=Tp, jb=jb,
                               split=split, nk=nk, quant=scales is not None)
    out = pl.pallas_call(
        kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hg, J // jb, R, W), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (3 if split else 2)
            + ("arbitrary",)),
        interpret=interpret,
    )(layer, tables, start, end, *operands)

    # the row of query head r of KV head h holds its output in lane block h
    out = out[:, :, :, :jb * Tp, :G * D].reshape(B, Hg, G, q_per_kv, Tp,
                                                 G, D)
    out = jnp.moveaxis(jnp.diagonal(out, axis1=2, axis2=5), -1, 2)
    out = out.reshape(B, Hq, Tp, D)[:, :, :T]
    return out.transpose(0, 2, 1, 3)


def _paged_decode(q, k_pool, v_pool, scales, block_tables, cache_len,
                  **kw):
    """Decode is a one-row chunk at position ``cache_len - 1``."""
    cache_len = jnp.asarray(cache_len, jnp.int32)
    out = _paged_attention(q[:, None], k_pool, v_pool, scales, block_tables,
                           cache_len - 1, 1, prefix_len=0, **kw)
    return out[:, 0]


def paged_decode_attention_pallas(q, k_pages, v_pages, block_tables,
                                  cache_len, *, kv_heads: int, layer=0,
                                  softmax_scale=None, interpret=False):
    """q: (B, Hq, D); pages: the arena's stacked pools ``(layers, Hkv/G,
    P, block_size, W)`` (``paged_pool``) holding ``kv_heads`` KV heads
    (``paged_pool.geometry``); block_tables: (B, blocks_per_slot) int32;
    cache_len: (B,) int32; ``layer`` selects the layer.  Returns (B, Hq,
    D).

    The layer index and the block table ride in scalar-prefetch SMEM, so
    each grid step's BlockSpec index map names the ``(layer, head group,
    physical page)`` tile: the kernel streams pages in logical order out
    of the stored pool, with no slice, relayout or gather of it.
    """
    return _paged_decode(q, k_pages, v_pages, None, block_tables, cache_len,
                         layer=layer, kv_heads=kv_heads,
                         softmax_scale=softmax_scale, interpret=interpret,
                         name="paged_decode_attention")


def paged_decode_attention_quant_pallas(q, k_pages, v_pages, k_scales,
                                        v_scales, block_tables, cache_len,
                                        *, kv_heads: int, layer=0,
                                        softmax_scale=None, interpret=False):
    """Quantized sibling of ``paged_decode_attention_pallas``: int8 value
    pools and their f32 scale rows ``(layers, Hkv/G, rows, 128)``; the
    kernel streams int8 tiles plus one scale row per page and applies the
    scales to the scores and probabilities in-register — HBM decode
    traffic is 1 byte per KV element plus 4/D bytes of scale."""
    return _paged_decode(q, k_pages, v_pages, (k_scales, v_scales),
                         block_tables, cache_len, layer=layer,
                         kv_heads=kv_heads, softmax_scale=softmax_scale,
                         interpret=interpret,
                         name="paged_decode_attention_int8")


def paged_chunk_prefill_attention_pallas(q, k_pages, v_pages, block_tables,
                                         start, chunk_len, *,
                                         kv_heads: int, layer=0,
                                         prefix_len: int = 0,
                                         softmax_scale=None,
                                         interpret=False):
    """Chunked-prefill attention straight through the serving arena's block
    table: q (B, T, Hq, D) chunk queries; pages the stacked pools
    ``(layers, Hkv/G, P, block_size, W)`` read at ``layer``, holding
    ``kv_heads`` KV heads; block_tables (B, blocks_per_slot) int32;
    start/chunk_len (B,) int32.  The chunk's own K/V must already be
    scattered into the pages (the engine writes pages before attending).
    Returns (B, T, Hq, D).

    Like ``paged_decode_attention_pallas`` (a one-row chunk of this
    kernel), the layer and the table ride in scalar-prefetch SMEM so the
    K/V BlockSpec index maps stream physical pages in logical order;
    ``ops.paged_chunk_attention`` provides the dense-gather CPU fallback.
    A grid step holds every query head of a head group at every chunk
    position in one tile (split into row blocks only past
    ``_TILE_ELEMS``), so a chunk reads each page once per head group.

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
    return _paged_attention(q, k_pages, v_pages, None, block_tables, start,
                            chunk_len, layer=layer, kv_heads=kv_heads,
                            prefix_len=prefix_len,
                            softmax_scale=softmax_scale,
                            interpret=interpret,
                            name="paged_chunk_attention")


def paged_chunk_prefill_attention_quant_pallas(q, k_pages, v_pages,
                                               k_scales, v_scales,
                                               block_tables, start,
                                               chunk_len, *,
                                               kv_heads: int, layer=0,
                                               prefix_len: int = 0,
                                               softmax_scale=None,
                                               interpret=False):
    """Quantized sibling of ``paged_chunk_prefill_attention_pallas``: the
    chunk's own rows must already be *quantized* into the int8 pages (the
    write path quantizes before attending), so the kernel's dequantized
    view is exactly what decode will later read."""
    return _paged_attention(q, k_pages, v_pages, (k_scales, v_scales),
                            block_tables, start, chunk_len, layer=layer,
                            kv_heads=kv_heads, prefix_len=prefix_len,
                            softmax_scale=softmax_scale,
                            interpret=interpret,
                            name="paged_chunk_attention_int8")
