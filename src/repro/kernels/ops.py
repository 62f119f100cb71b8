"""Jit-friendly wrappers over the Pallas kernels and their jnp oracles.

Every op takes ``impl``:
  * ``"ref"``               — memory-bounded pure-jnp path (XLA): the CPU
                              path, and the compiled multi-pod dry-run.
  * ``"pallas"``            — the TPU kernel (deployment target).
  * ``"pallas_interpret"``  — the TPU kernel body interpreted on CPU; used
                              by tests to validate kernels vs the oracles.

``default_impl()`` resolves the backend's path: ``pallas`` on a TPU,
``ref`` elsewhere.  REPRO_KERNEL_IMPL forces a value, but ``pallas`` off a
TPU is an error — it never degrades to interpret mode.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import paged_pool, ref
from .decode_attention import (chunk_prefill_attention_pallas,
                               decode_attention_pallas, mask_block_tables,
                               paged_chunk_prefill_attention_pallas,
                               paged_chunk_prefill_attention_quant_pallas,
                               paged_decode_attention_pallas,
                               paged_decode_attention_quant_pallas)
from .flash_attention import flash_attention_pallas
from .moe_gemm import grouped_matmul_pallas
from .quant import QuantPages
from .ssd_scan import ssd_scan_pallas

VALID_IMPLS = ("ref", "pallas", "pallas_interpret")


def default_impl() -> str:
    backend = jax.default_backend()
    impl = os.environ.get("REPRO_KERNEL_IMPL")
    if impl is None:
        return "pallas" if backend == "tpu" else "ref"
    if impl not in VALID_IMPLS:
        raise ValueError(f"REPRO_KERNEL_IMPL={impl!r}; want one of {VALID_IMPLS}")
    if impl == "pallas" and backend != "tpu":
        raise ValueError(
            f"REPRO_KERNEL_IMPL=pallas needs a TPU backend, got {backend!r} "
            f"(use pallas_interpret to run the kernel bodies on {backend})")
    return impl


def _split_heads(kernel, head_axes, out_head_axis: int):
    """``kernel`` run once per shard of the ambient mesh's ``model`` axis.

    XLA cannot partition a Mosaic kernel, so under a model-parallel mesh
    (``jax.set_mesh``) each operand's head axis (``None`` = replicated) is
    split with ``shard_map`` and every device attends over its own heads —
    attention never mixes heads.  Without such a mesh the kernel runs as
    is."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or dict(mesh.shape).get("model", 1) == 1:
        return kernel

    def spec(ax, ndim):
        return P(*("model" if d == ax else None for d in range(ndim)))

    def call(*args):
        args = [jnp.asarray(a) for a in args]
        in_specs = tuple(P() if ax is None else spec(ax, jnp.ndim(a))
                         for a, ax in zip(args, head_axes))
        out_nd = jnp.ndim(args[0])
        return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                             out_specs=spec(out_head_axis, out_nd),
                             check_vma=False)(*args)

    return call


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_cv(opts, q, k, v):
    out, _ = _flash_fwd(opts, q, k, v)
    return out


def _flash_fwd(opts, q, k, v):
    (causal, window, prefix_len, q_offset, kv_len, scale, impl) = opts
    if impl == "ref":
        out, lse = ref.flash_attention_fwd_ref(
            q, k, v, causal=causal, window=window, prefix_len=prefix_len,
            q_offset=q_offset, kv_len=kv_len, softmax_scale=scale)
    else:
        out, lse = flash_attention_pallas(
            q, k, v, causal=causal, window=window, prefix_len=prefix_len,
            q_offset=q_offset, kv_len=kv_len, softmax_scale=scale,
            return_lse=True, interpret=(impl == "pallas_interpret"))
    return out, (q, k, v, out, lse)


def _flash_bwd(opts, res, dout):
    (causal, window, prefix_len, q_offset, kv_len, scale, impl) = opts
    q, k, v, out, lse = res
    kwargs = dict(causal=causal, window=window, prefix_len=prefix_len,
                  q_offset=q_offset, kv_len=kv_len, softmax_scale=scale)
    if impl == "ref":
        return ref.flash_attention_bwd_ref(q, k, v, out, lse, dout,
                                           **kwargs)
    from .flash_attention_bwd import flash_attention_bwd_pallas
    return flash_attention_bwd_pallas(
        q, k, v, out, lse, dout,
        interpret=(impl == "pallas_interpret"), **kwargs)


_flash_cv.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                    prefix_len: int = 0, q_offset: int = 0,
                    kv_len: Optional[int] = None, softmax_scale=None,
                    impl: Optional[str] = None):
    """Flash attention with a recomputing (flash) backward — the O(S^2)
    attention matrix is never materialized in either pass, so training at
    32k context stays within HBM (EXPERIMENTS.md §Dry-run)."""
    impl = impl or default_impl()
    opts = (causal, window, prefix_len, q_offset, kv_len, softmax_scale,
            impl)
    return _flash_cv(opts, q, k, v)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None, softmax_scale=None,
                     impl: Optional[str] = None):
    impl = impl or default_impl()
    if impl == "ref":
        return ref.decode_attention_ref(
            q, k_cache, v_cache, cache_len, window=window,
            softmax_scale=softmax_scale)
    return decode_attention_pallas(
        q, k_cache, v_cache, cache_len, window=window,
        softmax_scale=softmax_scale, interpret=(impl == "pallas_interpret"))


def _paged_ref_kv(k_pages, v_pages, block_tables, valid_len, head_dim,
                  kv_heads, layer):
    """The ref fallback's K/V: per-slot rows gathered through a table
    clipped at ``valid_len`` (entries past it read the one trash page),
    int8 pools dequantized."""
    bt = mask_block_tables(block_tables, valid_len,
                           paged_pool.block_size_of(k_pages),
                           paged_pool.pages_of(k_pages) - 1)
    return tuple(paged_pool.gather(p, bt, head_dim, kv_heads, layer=layer)
                 for p in (k_pages, v_pages))


def _paged_kernel(fn, head_axis: int, q, k_pages, v_pages, tail, layer,
                  kv_heads, **kw):
    """``fn(q, *pool arrays, *tail, layer=layer, kv_heads=..., **kw)``,
    split over the mesh's ``model`` axis by head group: axis 1 of every
    pool array, ``head_axis`` of the queries and of the output.  A shard
    holds whole head groups, so its KV heads are the group size times its
    head groups."""
    pools = [k_pages, v_pages]
    if isinstance(k_pages, QuantPages):
        pools = [k_pages.values, v_pages.values, k_pages.scales,
                 v_pages.scales]
    group = paged_pool.geometry(pools[0], pools[2] if len(pools) == 4
                                else None, q.shape[-1], kv_heads).group

    def kernel(*args):
        *args, layer = args
        return fn(*args, layer=layer, kv_heads=group * args[1].shape[1],
                  **kw)

    axes = (head_axis,) + (1,) * len(pools) + (None,) * (len(tail) + 1)
    return _split_heads(kernel, axes, head_axis)(q, *pools, *tail, layer)


def paged_decode_attention(q, k_pages, v_pages, block_tables, cache_len, *,
                           kv_heads: int, layer=0, softmax_scale=None,
                           impl: Optional[str] = None):
    """Decode attention against the serving arena's stacked paged pools
    (``paged_pool`` layout) at ``layer`` — the families' paged-native
    decode hot path.  ``kv_heads`` is the pools' KV head count (which
    ``paged_pool.geometry`` needs to tell heads from padding lanes).

    ``"ref"`` gathers per-slot rows through a length-clipped block table
    (entries past ``cache_len`` route to the trash page, so the CPU
    fallback streams up-to-len rows instead of each slot's full pool) and
    runs the jnp oracle; the Pallas path streams K/V through the table via
    scalar prefetch and skips past-len blocks entirely.

    ``QuantPages`` pools (int8 values + f32 per-row scales) dispatch to the
    quantized kernel variants: the ref path gathers values AND scales
    through the same masked table and dequantizes before the oracle — the
    identical jnp math the in-kernel dequant reproduces.
    """
    impl = impl or default_impl()
    if impl == "ref":
        k, v = _paged_ref_kv(k_pages, v_pages, block_tables, cache_len,
                             q.shape[-1], kv_heads, layer)
        return ref.decode_attention_ref(q, k, v, cache_len,
                                        softmax_scale=softmax_scale)
    fn = (paged_decode_attention_quant_pallas
          if isinstance(k_pages, QuantPages)
          else paged_decode_attention_pallas)
    return _paged_kernel(fn, 1, q, k_pages, v_pages,
                         (block_tables, cache_len), layer, kv_heads,
                         softmax_scale=softmax_scale,
                         interpret=(impl == "pallas_interpret"))


def chunk_attention(q, k_cache, v_cache, start, chunk_len, *,
                    prefix_len: int = 0, softmax_scale=None,
                    impl: Optional[str] = None):
    """Chunked-prefill attention: T query rows at absolute positions
    ``start + i`` against a dense (B, S, Hkv, D) cache that already holds
    the chunk's own K/V (the piggybacked-prefill step writes the cache
    first, then attends).  ``start``/``chunk_len`` may be traced scalars or
    (B,) vectors — unlike ``flash_attention``'s static ``q_offset``, so one
    trace serves every chunk of a bucket size."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.chunk_attention_ref(q, k_cache, v_cache, start, chunk_len,
                                       prefix_len=prefix_len,
                                       softmax_scale=softmax_scale)
    return chunk_prefill_attention_pallas(
        q, k_cache, v_cache, start, chunk_len, prefix_len=prefix_len,
        softmax_scale=softmax_scale, interpret=(impl == "pallas_interpret"))


def paged_chunk_attention(q, k_pages, v_pages, block_tables, start,
                          chunk_len, *, kv_heads: int, layer=0,
                          prefix_len: int = 0, softmax_scale=None,
                          impl: Optional[str] = None):
    """Chunk-prefill attention against the serving arena's stacked paged
    pools at ``layer`` — the families' paged-native chunked-prefill hot
    path.

    ``"ref"`` gathers per-slot rows through a length-clipped block table
    (every attendable position sits below ``start + chunk_len``; entries
    past it route to the trash page) and runs the jnp chunk oracle; the
    Pallas path streams K/V through the table via scalar prefetch.
    ``QuantPages`` pools dispatch to the quantized variants, same contract
    (and ``kv_heads``) as ``paged_decode_attention``.
    """
    impl = impl or default_impl()
    if impl == "ref":
        end = jnp.asarray(start, jnp.int32) + jnp.asarray(chunk_len,
                                                          jnp.int32)
        k, v = _paged_ref_kv(k_pages, v_pages, block_tables, end,
                             q.shape[-1], kv_heads, layer)
        return ref.chunk_attention_ref(q, k, v, start, chunk_len,
                                       prefix_len=prefix_len,
                                       softmax_scale=softmax_scale)
    fn = (paged_chunk_prefill_attention_quant_pallas
          if isinstance(k_pages, QuantPages)
          else paged_chunk_prefill_attention_pallas)
    return _paged_kernel(fn, 2, q, k_pages, v_pages,
                         (block_tables, start, chunk_len), layer, kv_heads,
                         prefix_len=prefix_len, softmax_scale=softmax_scale,
                         interpret=(impl == "pallas_interpret"))


def paged_verify_attention(q, k_pages, v_pages, block_tables, start,
                           chunk_len, *, kv_heads: int, layer=0,
                           prefix_len: int = 0,
                           softmax_scale=None, impl: Optional[str] = None):
    """Speculative-decoding k-token verify against the paged KV layout —
    the SAME kernel path as ``paged_chunk_attention``, restated as the
    verify contract so the engine's one-fused-launch scoring of k draft
    tokens plus the bonus position is pinned down next to the kernels:

    * ``q`` carries T = k+1 rows per slot, the fed tokens
      ``[last_emitted, d_1 .. d_k]`` at absolute positions
      ``start + i``;
    * ``chunk_len`` MUST be a per-slot (B,) vector — T for slots
      speculating this round, 0 for every other row of the fixed-capacity
      batch.  Zero-length rows attend over nothing (their outputs are
      garbage/NaN the verifier masks) and their K/V writes were already
      routed to the trash block by ``paged_insert_rows``;
    * causality inside the chunk is the standard chunk mask: row ``i``
      sees cache positions ``< start + i + 1``, so each draft token is
      scored against exactly the prefix it would have been decoded after
      — which is what makes greedy verify bit-identical to the
      non-speculative oracle, one token per launch.

    No new kernel: verification IS chunked prefill with a per-slot length
    vector (``chunk_prefill_attention_pallas`` /
    ``paged_chunk_prefill_attention_pallas`` already take (B,) lengths
    via scalar prefetch — see ``kernels/decode_attention.py``), so the
    bf16/int8 dispatch and the trash-block masking are inherited
    unchanged."""
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    if chunk_len.ndim != 1:
        raise ValueError(
            f"paged_verify_attention requires a per-slot (B,) chunk_len "
            f"vector (0 = row not speculating), got shape "
            f"{chunk_len.shape}")
    return paged_chunk_attention(q, k_pages, v_pages, block_tables, start,
                                 chunk_len, layer=layer, kv_heads=kv_heads,
                                 prefix_len=prefix_len,
                                 softmax_scale=softmax_scale, impl=impl)


def ssd_scan(x, dt, A, B, C, D=None, *, chunk: int = 128,
             initial_state=None, impl: Optional[str] = None):
    impl = impl or default_impl()
    if impl == "ref":
        return ref.ssd_chunked_ref(x, dt, A, B, C, D, chunk=chunk,
                                   initial_state=initial_state)
    return ssd_scan_pallas(x, dt, A, B, C, D, chunk=chunk,
                           initial_state=initial_state,
                           interpret=(impl == "pallas_interpret"))


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t, D=None):
    # elementwise-dominated; the jnp path is already optimal on TPU.
    return ref.ssd_decode_step_ref(state, x_t, dt_t, A, B_t, C_t, D)


def grouped_matmul(lhs, rhs, *, impl: Optional[str] = None):
    impl = impl or default_impl()
    if impl == "ref":
        return ref.grouped_matmul_ref(lhs, rhs)
    return grouped_matmul_pallas(lhs, rhs,
                                 interpret=(impl == "pallas_interpret"))
