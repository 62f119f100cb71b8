"""Pallas TPU grouped (per-expert) matmul kernel.

Computes (E, C, K) @ (E, K, N) -> (E, C, N) — the expert-FFN GEMM after
capacity-based dispatch.  Grid = (E, C_blocks, N_blocks, K_blocks) with the
contraction dimension sequential and an fp32 accumulator tile in VMEM, so
arbitrary K (d_model or d_ff, up to 32k for grok) streams through VMEM in
MXU-aligned tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_C = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 512


def _gemm_kernel(lhs_ref, rhs_ref, out_ref, acc_ref, *, nk: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[0], rhs_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def grouped_matmul_pallas(lhs, rhs, *, block_c=DEFAULT_BLOCK_C,
                          block_n=DEFAULT_BLOCK_N, block_k=DEFAULT_BLOCK_K,
                          interpret=False):
    """lhs: (E, C, K); rhs: (E, K, N) -> (E, C, N)."""
    E, C, K = lhs.shape
    _, _, N = rhs.shape
    block_c = min(block_c, max(8, C))
    block_n = min(block_n, max(8, N))
    block_k = min(block_k, max(8, K))
    Cp = -(-C // block_c) * block_c
    Kp = -(-K // block_k) * block_k
    Np = -(-N // block_n) * block_n
    lp = jnp.pad(lhs, ((0, 0), (0, Cp - C), (0, Kp - K)))
    rp = jnp.pad(rhs, ((0, 0), (0, Kp - K), (0, Np - N)))

    nk = Kp // block_k
    grid = (E, Cp // block_c, Np // block_n, nk)
    kernel = functools.partial(_gemm_kernel, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_k),
                         lambda e, ci, ni, ki: (e, ci, ki)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda e, ci, ni, ki: (e, ki, ni)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_n),
                               lambda e, ci, ni, ki: (e, ci, ni)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, Np), lhs.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(lp, rp)
    return out[:, :C, :N]
