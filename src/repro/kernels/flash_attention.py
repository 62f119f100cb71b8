"""Pallas TPU flash-attention (prefill/training) kernel.

TPU-native adaptation: online-softmax over KV blocks streamed through VMEM,
MXU-aligned (128x128 default) tiles, grid = (batch*q_heads, q_blocks,
kv_blocks) with the kv dimension sequential ("arbitrary") carrying the
(m, l, acc) running statistics in VMEM scratch.  GQA is handled by index
mapping: the kv operand is indexed by ``bh // group`` so kv tiles are
fetched from the shared kv head.

Supports: causal, sliding-window, bidirectional prefix (prefix-LM), valid
kv-length masking, and a query offset (for chunked prefill) — the same
semantics as ``ref.mha_exact``.

Validated on CPU with ``interpret=True`` against ``ref.py``; compiled for
TPU as the deployment target.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF

DEFAULT_Q_BLOCK = 128
DEFAULT_K_BLOCK = 128
_LANES = 128  # TPU lane width for the (m, l) statistic tiles


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  prefix_len: int, q_offset: int, kv_len: int,
                  q_block: int, k_block: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level visibility: skip fully-masked kv blocks (this is what makes
    # the kernel sub-quadratic for sliding-window attention).
    q_lo = q_offset + qi * q_block          # first query position in tile
    q_hi = q_lo + q_block - 1
    k_lo = ki * k_block
    k_hi = k_lo + k_block - 1
    visible = k_lo < kv_len
    if causal:
        visible = jnp.logical_and(visible, k_lo <= q_hi)
        if window is not None:
            in_window = k_hi > q_lo - window
            if prefix_len > 0:
                in_window = jnp.logical_or(in_window, k_lo < prefix_len)
            visible = jnp.logical_and(visible, in_window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (q_block, k_block), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (q_block, k_block), 1)
        ok = kpos < kv_len
        if causal:
            c = kpos <= qpos
            if window is not None:
                c = jnp.logical_and(c, kpos > qpos - window)
            if prefix_len > 0:
                c = jnp.logical_or(c, kpos < prefix_len)
            ok = jnp.logical_and(ok, c)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                       # (q_block, LANES), cols equal
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # mask p: fully-masked rows would otherwise get exp(0) == 1
        p = jnp.exp(s - m_new[:, :1]) * ok.astype(jnp.float32)
        l_ref[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)
        # log-sum-exp residual for the recomputing backward; fully-masked
        # rows get -NEG_INF (large positive) so exp(s - lse) == 0 there
        m = m_ref[...][:, :1]
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)),
                        -NEG_INF)
        lse_ref[0] = lse


def flash_attention_pallas(q, k, v, *, causal=True, window=None, prefix_len=0,
                           q_offset=0, kv_len=None, softmax_scale=None,
                           q_block=DEFAULT_Q_BLOCK, k_block=DEFAULT_K_BLOCK,
                           return_lse=False, interpret=False):
    """q: (B, Lq, Hq, D); k, v: (B, Lk, Hkv, D) -> (B, Lq, Hq, D)
    [, lse (B, Lq, Hq) when return_lse]."""
    B, Lq, Hq, D = q.shape
    _, Lk, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    kv_len = Lk if kv_len is None else kv_len

    q_block = min(q_block, max(8, Lq))
    k_block = min(k_block, max(8, Lk))
    Lq_p = -(-Lq // q_block) * q_block
    Lk_p = -(-Lk // k_block) * k_block

    qt = jnp.pad(q, ((0, 0), (0, Lq_p - Lq), (0, 0), (0, 0)))
    kt = jnp.pad(k, ((0, 0), (0, Lk_p - Lk), (0, 0), (0, 0)))
    vt = jnp.pad(v, ((0, 0), (0, Lk_p - Lk), (0, 0), (0, 0)))
    # (B, L, H, D) -> (B*H, L, D)
    qt = qt.transpose(0, 2, 1, 3).reshape(B * Hq, Lq_p, D)
    kt = kt.transpose(0, 2, 1, 3).reshape(B * Hkv, Lk_p, D)
    vt = vt.transpose(0, 2, 1, 3).reshape(B * Hkv, Lk_p, D)

    nq = Lq_p // q_block
    nk = Lk_p // k_block
    grid = (B * Hq, nq, nk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        prefix_len=prefix_len, q_offset=q_offset, kv_len=kv_len,
        q_block=q_block, k_block=k_block, nk=nk)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, k_block, D),
                         lambda bh, qi, ki, group=group: (bh // group, ki, 0)),
            pl.BlockSpec((1, k_block, D),
                         lambda bh, qi, ki, group=group: (bh // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q_block, D),
                         lambda bh, qi, ki: (bh, qi, 0)),
            # a trailing unit axis keeps the block's last two dims
            # (q_block, 1) within the TPU's (8, 128) tiling rule
            pl.BlockSpec((1, q_block, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Lq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, Lq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_block, _LANES), jnp.float32),   # m
            pltpu.VMEM((q_block, _LANES), jnp.float32),   # l
            pltpu.VMEM((q_block, D), jnp.float32),        # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)

    out = out.reshape(B, Hq, Lq_p, D).transpose(0, 2, 1, 3)[:, :Lq]
    if return_lse:
        lse = lse.reshape(B, Hq, Lq_p).transpose(0, 2, 1)[:, :Lq]
        return out, lse
    return out
