"""Pallas TPU flash-attention BACKWARD kernels.

Standard FlashAttention-2 split:

  dq kernel   — grid (B*Hq, q_blocks, kv_blocks): recompute P per tile from
                (q, k, lse), dS = P*(dP - delta), accumulate dq in VMEM
                scratch over the sequential kv dimension.
  dkdv kernel — grid (B*Hkv, kv_blocks, G*q_blocks): the GQA group and the
                q-block loop are folded into one sequential dimension, so
                dk/dv accumulate contributions from every query head that
                shares the kv head without inter-step races.

Inputs are the fwd residuals: lse (log-sum-exp per row) and
delta = rowsum(dout * out), both computed by the thin jnp wrapper.
Semantics (masks, scaling) match ``ref.flash_attention_bwd_ref`` exactly;
validated in interpret mode by tests/test_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF

DEFAULT_BLOCK = 128


def _mask_tile(q_lo, k_lo, q_block, k_block, *, causal, window, prefix_len,
               kv_len):
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
    return ok


def _block_visible(q_lo, q_hi, k_lo, k_hi, *, causal, window, prefix_len,
                   kv_len):
    visible = k_lo < kv_len
    if causal:
        visible = jnp.logical_and(visible, k_lo <= q_hi)
        if window is not None:
            in_w = k_hi > q_lo - window
            if prefix_len > 0:
                in_w = jnp.logical_or(in_w, k_lo < prefix_len)
            visible = jnp.logical_and(visible, in_w)
    return visible


def _recompute_p_ds(q, k, v, do, lse, delta, mask, scale):
    """Shared tile math: returns (p, ds) in f32."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[:, :1]) * mask.astype(jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, :1]) * scale
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref,
               acc_ref, *, scale, causal, window, prefix_len, q_offset,
               kv_len, q_block, k_block, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = q_offset + qi * q_block
    k_lo = ki * k_block
    visible = _block_visible(q_lo, q_lo + q_block - 1, k_lo,
                             k_lo + k_block - 1, causal=causal,
                             window=window, prefix_len=prefix_len,
                             kv_len=kv_len)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        mask = _mask_tile(q_lo, k_lo, q_block, k_block, causal=causal,
                          window=window, prefix_len=prefix_len,
                          kv_len=kv_len)
        _, ds = _recompute_p_ds(q, k, v, do, lse_ref[0][:, None],
                                dlt_ref[0][:, None], mask, scale)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dk_ref,
                 dv_ref, dk_acc, dv_acc, *, scale, causal, window,
                 prefix_len, q_offset, kv_len, q_block, k_block, nq,
                 nj):
    ki = pl.program_id(1)
    j = pl.program_id(2)          # folded (group, q_block) index
    qi = j % nq

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_lo = q_offset + qi * q_block
    k_lo = ki * k_block
    visible = _block_visible(q_lo, q_lo + q_block - 1, k_lo,
                             k_lo + k_block - 1, causal=causal,
                             window=window, prefix_len=prefix_len,
                             kv_len=kv_len)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        mask = _mask_tile(q_lo, k_lo, q_block, k_block, causal=causal,
                          window=window, prefix_len=prefix_len,
                          kv_len=kv_len)
        p, ds = _recompute_p_ds(q, k, v, do, lse_ref[0][:, None],
                                dlt_ref[0][:, None], mask, scale)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, out, lse, dout, *, causal=True,
                               window: Optional[int] = None,
                               prefix_len: int = 0, q_offset: int = 0,
                               kv_len: Optional[int] = None,
                               softmax_scale=None,
                               q_block: int = DEFAULT_BLOCK,
                               k_block: int = DEFAULT_BLOCK,
                               interpret: bool = False):
    """Same signature/semantics as ``ref.flash_attention_bwd_ref``."""
    B, Lq, Hq, D = q.shape
    _, Lk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    kv_len = Lk if kv_len is None else kv_len
    q_block = min(q_block, max(8, Lq))
    k_block = min(k_block, max(8, Lk))
    Lq_p = -(-Lq // q_block) * q_block
    Lk_p = -(-Lk // k_block) * k_block

    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                              # (B, Lq, Hq)

    def to_bh(a, H):  # (B, L, H, D) -> (B*H, Lp, D)
        L, pad = a.shape[1], (Lq_p if a.shape[1] == Lq else Lk_p) - a.shape[1]
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return a.transpose(0, 2, 1, 3).reshape(B * H, a.shape[1], D)

    qt = to_bh(q, Hq)
    kt = to_bh(k, Hkv)
    vt = to_bh(v, Hkv)
    dot_ = to_bh(dout, Hq)
    # padded lse rows must kill p: fill with -NEG_INF (large positive)
    lse_t = jnp.pad(lse, ((0, 0), (0, Lq_p - Lq), (0, 0)),
                    constant_values=-NEG_INF)
    lse_t = lse_t.transpose(0, 2, 1).reshape(B * Hq, Lq_p)
    dlt_t = jnp.pad(delta, ((0, 0), (0, Lq_p - Lq), (0, 0)))
    dlt_t = dlt_t.transpose(0, 2, 1).reshape(B * Hq, Lq_p)

    nq, nk = Lq_p // q_block, Lk_p // k_block
    common = dict(scale=scale, causal=causal, window=window,
                  prefix_len=prefix_len, q_offset=q_offset, kv_len=kv_len,
                  q_block=q_block, k_block=k_block)

    # ---- dq ---------------------------------------------------------------
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **common),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, k_block, D),
                         lambda bh, qi, ki, g=G: (bh // g, ki, 0)),
            pl.BlockSpec((1, k_block, D),
                         lambda bh, qi, ki, g=G: (bh // g, ki, 0)),
            pl.BlockSpec((1, q_block, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, q_block), lambda bh, qi, ki: (bh, qi)),
            pl.BlockSpec((1, q_block), lambda bh, qi, ki: (bh, qi)),
        ],
        out_specs=pl.BlockSpec((1, q_block, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Lq_p, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((q_block, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt, dot_, lse_t, dlt_t)

    # ---- dk, dv -------------------------------------------------------------
    nj = G * nq
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, nq=nq, nj=nj, **common),
        grid=(B * Hkv, nk, nj),
        in_specs=[
            pl.BlockSpec((1, q_block, D),
                         lambda bkv, ki, j, g=G, n=nq:
                         (bkv * g + j // n, j % n, 0)),
            pl.BlockSpec((1, k_block, D), lambda bkv, ki, j: (bkv, ki, 0)),
            pl.BlockSpec((1, k_block, D), lambda bkv, ki, j: (bkv, ki, 0)),
            pl.BlockSpec((1, q_block, D),
                         lambda bkv, ki, j, g=G, n=nq:
                         (bkv * g + j // n, j % n, 0)),
            pl.BlockSpec((1, q_block),
                         lambda bkv, ki, j, g=G, n=nq:
                         (bkv * g + j // n, j % n)),
            pl.BlockSpec((1, q_block),
                         lambda bkv, ki, j, g=G, n=nq:
                         (bkv * g + j // n, j % n)),
        ],
        out_specs=[
            pl.BlockSpec((1, k_block, D), lambda bkv, ki, j: (bkv, ki, 0)),
            pl.BlockSpec((1, k_block, D), lambda bkv, ki, j: (bkv, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, Lk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, Lk_p, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((k_block, D), jnp.float32),
                        pltpu.VMEM((k_block, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt, dot_, lse_t, dlt_t)

    def from_bh(a, H, L):
        return a.reshape(B, H, -1, D).transpose(0, 2, 1, 3)[:, :L]

    return (from_bh(dq, Hq, Lq), from_bh(dk, Hkv, Lk), from_bh(dv, Hkv, Lk))
