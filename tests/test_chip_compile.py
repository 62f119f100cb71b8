"""Compile the serving kernels for a TPU v5e that is described, not attached.

Interpret mode checks what a kernel computes; only the TPU compiler checks
that its block shapes, memory spaces and scratch fit the chip.  Each test
lowers one kernel at a real model's widths against a described ``v5e:2x2``
topology and asserts the Mosaic kernel (``tpu_custom_call``) made it into
the compiled program.  Nothing runs, so these say nothing about speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (
    paged_chunk_prefill_attention_pallas,
    paged_chunk_prefill_attention_quant_pallas, paged_decode_attention_pallas,
    paged_decode_attention_quant_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gemm import grouped_matmul_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

# minicpm-2b attention widths (MHA, 36 heads x 64), the arena's 32-token
# blocks, 16 slots x 64 blocks (2,048 tokens a slot) plus the trash page
HEADS, HEAD_DIM, BLOCK, SLOTS, SLOT_BLOCKS = 36, 64, 32, 16, 64
PAGES = SLOTS * SLOT_BLOCKS + 1


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_for_chip(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _pools(dtype, one_chip):
    shape = (PAGES, BLOCK, HEADS, HEAD_DIM)
    if dtype == "int8":
        return (_spec(shape, jnp.int8, one_chip),
                _spec(shape, jnp.int8, one_chip),
                _spec(shape[:-1], jnp.float32, one_chip),
                _spec(shape[:-1], jnp.float32, one_chip))
    return (_spec(shape, jnp.bfloat16, one_chip),
            _spec(shape, jnp.bfloat16, one_chip))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_compiles(one_chip, kv):
    q = _spec((SLOTS, HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    tables = _spec((SLOTS, SLOT_BLOCKS), jnp.int32, one_chip)
    lens = _spec((SLOTS,), jnp.int32, one_chip)
    if kv == "int8":
        _compile_for_chip(paged_decode_attention_quant_pallas, q,
                          *_pools(kv, one_chip), tables, lens)
    else:
        _compile_for_chip(paged_decode_attention_pallas, q,
                          *_pools(kv, one_chip), tables, lens)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("rows,T", [(1, 256), (SLOTS, 5)],
                         ids=["chunk256", "verify5"])
def test_paged_chunk_and_verify_compile(one_chip, kv, rows, T):
    """T=256 is a chunked-prefill bucket for one slot; T=5 is a k=4
    speculative verify across every slot (same kernel, per-slot lengths)."""
    q = _spec((rows, T, HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    tables = _spec((rows, SLOT_BLOCKS), jnp.int32, one_chip)
    start = _spec((rows,), jnp.int32, one_chip)
    n = _spec((rows,), jnp.int32, one_chip)
    fn = (paged_chunk_prefill_attention_quant_pallas if kv == "int8"
          else paged_chunk_prefill_attention_pallas)
    _compile_for_chip(fn, q, *_pools(kv, one_chip), tables, start, n)


def test_flash_prefill_compiles(one_chip):
    q = _spec((1, 2048, HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    _compile_for_chip(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                               return_lse=True),
        q, q, q)


def test_ssd_scan_compiles(one_chip):
    # mamba2-2.7b: d_inner 5120 = 80 heads x 64, state 128, one group,
    # the config's 256-token chunks
    L, H, P, N = 1024, 80, 64, 128
    x = _spec((1, L, H, P), jnp.bfloat16, one_chip)
    dt = _spec((1, L, H), jnp.float32, one_chip)
    A = _spec((H,), jnp.float32, one_chip)
    BC = _spec((1, L, 1, N), jnp.bfloat16, one_chip)
    D = _spec((H,), jnp.float32, one_chip)
    _compile_for_chip(
        lambda x, dt, A, B, C, D: ssd_scan_pallas(x, dt, A, B, C, D,
                                                  chunk=256),
        x, dt, A, BC, BC, D)


def test_grouped_matmul_compiles(one_chip):
    # mixtral-8x7b expert FFN: 8 experts, 512 routed rows each,
    # d_model 4096 -> d_ff 14336
    lhs = _spec((8, 512, 4096), jnp.bfloat16, one_chip)
    rhs = _spec((8, 4096, 14336), jnp.bfloat16, one_chip)
    _compile_for_chip(grouped_matmul_pallas, lhs, rhs)
