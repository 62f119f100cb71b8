"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation of the SSD ("state-space duality") algorithm: the sequence is
split into chunks of Q tokens; within a chunk the output is a masked
(Q x Q) matmul (MXU-friendly), across chunks a (P x N) state is carried
sequentially in VMEM scratch.  Grid = (batch*heads, chunks) with the chunk
dimension "arbitrary" (sequential) so the state scratch implements the
recurrence; batch*heads is embarrassingly parallel.

Inputs are laid out per (b, h):
  x  : (BH, L, P)      head channels
  dt : (BH, L, 1)      softplus-discretized step
  B  : (BH, L, N)      input projection (group-broadcast upstream)
  C  : (BH, L, N)      output projection
  A  : (BH,)          per-head negative decay (whole vector in SMEM)
  h0 : (BH, P, N)      initial state
Outputs: y (BH, L, P) and final state (BH, P, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref,
                state_ref, *, chunk: int, nc: int, seq_len: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = h0_ref[0].astype(jnp.float32)

    A = a_ref[pl.program_id(0)]
    x = x_ref[0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)          # (Q, 1)
    Bm = b_ref[0].astype(jnp.float32)           # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)           # (Q, N)

    # zero out padded tail tokens (dt=0 -> identity step, zero input)
    tpos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    dt = jnp.where(tpos < seq_len, dt, 0.0)

    logdA = dt * A                               # (Q, 1), <= 0
    q_iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = q_iota >= s_iota
    # Mosaic has no cumsum or vector transpose, so the inclusive prefix
    # sums come out of exact-f32 matmuls against triangular masks, once
    # down the rows (cum_t) and once across the columns (cum_s, dt_s)
    ones = jnp.ones((chunk, chunk), jnp.float32)
    cum_t = _dot(tri.astype(jnp.float32),
                 jnp.broadcast_to(logdA, (chunk, chunk)))       # [t, s] = cum_t
    cum_s = _dot(ones, jnp.where(q_iota <= s_iota, logdA, 0.0))  # [t, s] = cum_s
    dt_s = _dot(ones, jnp.where(q_iota == s_iota, dt, 0.0))      # [t, s] = dt_s
    cum = cum_t[:, :1]                           # (Q, 1) inclusive
    # intra-chunk: M[t, s] = exp(cum_t - cum_s) * (C_t . B_s) * dt_s, s <= t
    decay = jnp.exp(cum_t - cum_s)               # (Q, Q)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    M = jnp.where(tri, decay * cb * dt_s, 0.0)
    y_intra = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y_inter[t] = exp(cum_t) * C_t . h_prev
    h = state_ref[...]                           # (P, N)
    ch = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, P)
    y_inter = jnp.exp(cum) * ch

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = exp(cum_last) * h + sum_s exp(cum_last - cum_s) dt_s x_s B_s^T
    last = jnp.sum(logdA, axis=0, keepdims=True)  # (1, 1) = cum_{Q-1}
    w = jnp.exp(last - cum) * dt                 # (Q, 1)
    xw = x * w                                   # (Q, P)
    S = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (P, N)
    state_ref[...] = h * jnp.exp(last) + S

    @pl.when(ci == nc - 1)
    def _finalize():
        hout_ref[0] = state_ref[...].astype(hout_ref.dtype)


def ssd_scan_pallas(x, dt, A, B, C, D=None, *, chunk=DEFAULT_CHUNK,
                    initial_state=None, interpret=False):
    """Semantics of ``ref.ssd_chunked_ref`` (group-broadcast + flatten here).

    x : (Bb, L, H, P); dt : (Bb, L, H); A : (H,); B, C : (Bb, L, G, N).
    Returns (y (Bb, L, H, P), state (Bb, H, P, N)).
    """
    Bb, L, H, P = x.shape
    _, _, G, N = B.shape
    rep = H // G
    Q = min(chunk, max(8, L))
    Lp = -(-L // Q) * Q

    def padt(a):
        return jnp.pad(a, ((0, 0), (0, Lp - L)) + ((0, 0),) * (a.ndim - 2))

    xt = padt(x).transpose(0, 2, 1, 3).reshape(Bb * H, Lp, P)
    dtt = padt(dt).transpose(0, 2, 1).reshape(Bb * H, Lp, 1)
    Bh = jnp.repeat(padt(B), rep, axis=2).transpose(0, 2, 1, 3)
    Ch = jnp.repeat(padt(C), rep, axis=2).transpose(0, 2, 1, 3)
    Bh = Bh.reshape(Bb * H, Lp, N)
    Ch = Ch.reshape(Bb * H, Lp, N)
    Ab = jnp.broadcast_to(A[None], (Bb, H)).reshape(Bb * H)
    Ab = Ab.astype(jnp.float32)
    h0 = (jnp.zeros((Bb, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    h0 = h0.reshape(Bb * H, P, N)

    nc = Lp // Q
    grid = (Bb * H, nc)
    kernel = functools.partial(_ssd_kernel, chunk=Q, nc=nc, seq_len=L)

    y, hout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # the TPU refuses a (1, 1) SMEM block of an (BH, 1) array, so
            # the whole decay vector sits in SMEM, indexed by program id
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Q, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, Q, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb * H, Lp, P), x.dtype),
            jax.ShapeDtypeStruct((Bb * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(Ab, xt, dtt, Bh, Ch, h0)

    y = y.reshape(Bb, H, Lp, P).transpose(0, 2, 1, 3)[:, :L]
    if D is not None:
        y = (y.astype(jnp.float32)
             + x.astype(jnp.float32) * D[None, None, :, None]).astype(x.dtype)
    return y, hout.reshape(Bb, H, P, N)
