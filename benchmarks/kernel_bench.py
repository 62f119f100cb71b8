"""Kernel micro-benchmarks: wall time of the memory-bounded jnp oracles
(XLA-compiled; the TPU path is the Pallas kernel, validated in interpret
mode by tests) plus derived FLOP/s, at serving-representative shapes."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, paged_pool, ref


def _bench(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run() -> list:
    rows = []
    key = jax.random.PRNGKey(0)
    # flash attention prefill (B=1, L=2048, GQA 8/2)
    B, L, Hq, Hkv, D = 1, 2048, 8, 2, 64
    q = jax.random.normal(key, (B, L, Hq, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, L, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, L, Hkv, D), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    us = _bench(f, q, k, v)
    flops = 4 * B * Hq * L * L * D / 2      # causal half
    rows.append(("kernel/flash_prefill_2k", us,
                 f"{flops / (us * 1e-6) / 1e9:.1f}GFLOPs"))
    # decode vs 32k cache
    S = 32768
    qd = jax.random.normal(key, (4, Hq, D), jnp.bfloat16)
    kc = jax.random.normal(key, (4, S, Hkv, D), jnp.bfloat16)
    vc = jax.random.normal(key, (4, S, Hkv, D), jnp.bfloat16)
    fd = jax.jit(lambda q, k, v: ref.decode_attention_ref(q, k, v, S))
    us = _bench(fd, qd, kc, vc)
    bytes_ = 2 * 4 * S * Hkv * D * 2
    rows.append(("kernel/decode_32k", us,
                 f"{bytes_ / (us * 1e-6) / 1e9:.1f}GB_s"))
    # SSD chunked scan (mamba2-ish slice)
    Bb, Lx, H, P, N = 2, 2048, 8, 64, 64
    x = jax.random.normal(key, (Bb, Lx, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(key, (Bb, Lx, H)))
    A = -jnp.exp(jax.random.normal(key, (H,)))
    Bm = jax.random.normal(key, (Bb, Lx, 1, N))
    C = jax.random.normal(key, (Bb, Lx, 1, N))
    fs = jax.jit(lambda *a: ref.ssd_chunked_ref(*a, chunk=128)[0])
    us = _bench(fs, x, dt, A, Bm, C)
    rows.append(("kernel/ssd_2k", us,
                 f"{Bb * Lx * H / (us * 1e-6) / 1e6:.2f}Mtok_heads_s"))
    # paged decode, bf16 vs int8 pools (same table/lens; the int8 case
    # streams half the K/V bytes plus the f32 per-row scales and
    # dequantizes in-register — the serving arena's quantized hot path)
    bs, P = 32, 64 * 4 + 1                 # 4 slots x 64 blocks + trash
    Bp = 4
    # natural (1 layer, pages, block, heads, D) pools in the stored layout
    kn = jax.random.normal(key, (1, P, bs, Hkv, D), jnp.bfloat16)
    kp = vp = paged_pool.from_natural(kn)
    bt = jnp.arange(Bp * 64, dtype=jnp.int32).reshape(Bp, 64)
    cl = jnp.full((Bp,), 64 * bs, jnp.int32)
    qp = jax.random.normal(key, (Bp, Hq, D), jnp.bfloat16)
    fp = jax.jit(lambda q, k, v: ops.paged_decode_attention(
        q, k, v, bt, cl, kv_heads=Hkv, impl="ref"))
    us = _bench(fp, qp, kp, vp)
    kv_bytes = 2 * Bp * 64 * bs * Hkv * D * 2
    rows.append(("kernel/paged_decode_bf16", us,
                 f"{kv_bytes / (us * 1e-6) / 1e9:.1f}GB_s"))
    kq = vq = paged_pool.from_natural(kn, quantized=True)
    fq = jax.jit(lambda q, k, v: ops.paged_decode_attention(
        q, k, v, bt, cl, kv_heads=Hkv, impl="ref"))
    us = _bench(fq, qp, kq, vq)
    kv_bytes = 2 * Bp * 64 * bs * Hkv * (D * 1 + 4)   # int8 rows + scales
    rows.append(("kernel/paged_decode_int8", us,
                 f"{kv_bytes / (us * 1e-6) / 1e9:.1f}GB_s"))
    # grouped expert GEMM
    E, Cc, K, Nn = 8, 512, 1024, 1024
    lhs = jax.random.normal(key, (E, Cc, K), jnp.bfloat16)
    rhs = jax.random.normal(key, (E, K, Nn), jnp.bfloat16)
    fg = jax.jit(ref.grouped_matmul_ref)
    us = _bench(fg, lhs, rhs)
    flops = 2 * E * Cc * K * Nn
    rows.append(("kernel/moe_gemm", us,
                 f"{flops / (us * 1e-6) / 1e9:.1f}GFLOPs"))
    return rows
