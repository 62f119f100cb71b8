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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_pool
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
GROUP = paged_pool.head_group(HEAD_DIM, HEADS)


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


def _pools(dtype, one_chip, layers=2):
    """Stacked pools in the arena's stored layout (``paged_pool``)."""
    shape = paged_pool.value_shape(layers, PAGES, BLOCK, HEADS, HEAD_DIM,
                                   GROUP)
    if dtype == "int8":
        scales = paged_pool.scale_shape(layers, PAGES, BLOCK, HEADS, GROUP)
        return (_spec(shape, jnp.int8, one_chip),
                _spec(shape, jnp.int8, one_chip),
                _spec(scales, jnp.float32, one_chip),
                _spec(scales, jnp.float32, one_chip))
    return (_spec(shape, jnp.bfloat16, one_chip),
            _spec(shape, jnp.bfloat16, one_chip))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_compiles(one_chip, kv):
    q = _spec((SLOTS, HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    tables = _spec((SLOTS, SLOT_BLOCKS), jnp.int32, one_chip)
    lens = _spec((SLOTS,), jnp.int32, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    fn = (paged_decode_attention_quant_pallas if kv == "int8"
          else paged_decode_attention_pallas)
    _compile_for_chip(lambda *a: fn(*a[:-1], layer=a[-1], kv_heads=HEADS),
                      q, *_pools(kv, one_chip), tables, lens, layer)


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
    layer = _spec((), jnp.int32, one_chip)
    fn = (paged_chunk_prefill_attention_quant_pallas if kv == "int8"
          else paged_chunk_prefill_attention_pallas)
    _compile_for_chip(lambda *a: fn(*a[:-1], layer=a[-1], kv_heads=HEADS),
                      q, *_pools(kv, one_chip), tables, start, n, layer)


# the whole serving steps over 42 slots of 32 blocks at three attention
# geometries: minicpm-2b's 36 KV heads of 64 (2 to a row) in the benchmark
# cell's 1,345 pages; the 9 heads a 4-way model mesh leaves each chip of
# it (all 9 in a row of 640 lanes, 64 of them padding) in the four times
# as many pages such a chip holds; and zamba2-7b's 32 heads of 112 (8 to
# a row of 896).  Each pool is as deep as fits one chip beside the
# weights, and never small: XLA stages a pool that fits in on-chip memory
# (128 MiB on a v5e) through relayouts and copies a deployment's pool
# never gets.
STEP_GEOMETRIES = {"36x64": (36, 64, 1345, {"int8": 40, "bf16": 16}),
                   "9x64": (9, 64, 4 * 1344 + 1, {"int8": 40, "bf16": 16}),
                   "32x112": (32, 112, 1345, {"int8": 32, "bf16": 8})}
STEP_SLOTS, STEP_BLOCKS = 42, 32


def _serving_step_hlo(one_chip, geometry, kv, phase):
    """Optimized HLO of ``decode_step_paged`` (one token for every slot)
    or of a 128-token ``prefill_chunk_paged`` over pools as the arena
    allocates them, donated as the engine donates them."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import transformer
    from repro.serving.arena import KVArena
    heads, head_dim, pages, layers = STEP_GEOMETRIES[geometry]
    cfg = dataclasses.replace(get_config("minicpm-2b"),
                              num_layers=layers[kv], vocab_size=1024,
                              num_heads=heads, num_kv_heads=heads,
                              head_dim=head_dim)
    arena = KVArena(cfg, transformer.init_cache, capacity=1,
                    max_seq_len=STEP_BLOCKS * BLOCK, block_size=BLOCK,
                    kv_dtype=kv)
    place = lambda tree: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), tree)
    pools = place(arena.pool_structs(pages))
    params = place(jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), cfg)))
    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)

    def step(params, tokens, pools, lens, live, tables):
        cache = {"k": pools[0], "v": pools[1], "len": lens}
        if phase == "decode":
            logits, new = transformer.decode_step_paged(
                params, cfg, tokens, cache, tables, live, block_size=BLOCK,
                impl="pallas")
        else:
            logits, new = transformer.prefill_chunk_paged(
                params, cfg, {"tokens": tokens}, cache, tables,
                chunk_len=live, block_size=BLOCK, impl="pallas")
        return logits, [new["k"], new["v"]], new["len"]

    if phase == "decode":
        args = (i32(STEP_SLOTS), pools, i32(STEP_SLOTS),
                _spec((STEP_SLOTS,), jnp.bool_, one_chip),
                i32(STEP_SLOTS, STEP_BLOCKS))
    else:
        args = (i32(1, 128), pools, i32(1), i32(), i32(1, STEP_BLOCKS))
    text = jax.jit(step, donate_argnums=(2,)).lower(
        params, *args).compile().as_text()
    return text, pools


# an HLO instruction: its name, result shape and opcode
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([0-9,]*)\]\S* "
                    r"([a-z][a-z0-9-]*)\((.*)$")
# what may hold a whole pool: the donated buffer, the loop carry, views of
# it, the in-place row scatter, and the kernel that reads it
_IN_PLACE = {"parameter", "get-tuple-element", "bitcast", "scatter",
             "custom-call"}


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("phase", ["decode", "chunk"])
@pytest.mark.parametrize("geometry", list(STEP_GEOMETRIES))
def test_serving_step_reads_pools_in_place(one_chip, geometry, kv, phase):
    """The arena stores each pool in the layout the paged kernels read, so
    the compiled step moves no whole pool: every instruction shaped like
    one (by its page or scale-row count) is the donated buffer, the loop
    carry, a bitcast, the in-place row scatter or the kernel — no copy,
    transpose, slice or loop fusion — and the kernel's pool operands are
    the carry or the scatter's result, through bitcasts at most."""
    text, pools = _serving_step_hlo(one_chip, geometry, kv, phase)
    pool_dims = {str(a.shape[2]) for pool in pools
                 for a in jax.tree.leaves(pool)}
    bodies, name = {}, None       # fused computation -> its instructions
    for line in text.splitlines():
        if line.startswith("%"):
            name = line.split()[0].lstrip("%")
        bodies[name] = bodies.get(name, "") + line + "\n"

    def scatter_fusion(op, rest):
        called = re.search(r"calls=%([\w.-]+)", rest)
        return (op == "fusion" and "kind=kCustom" in rest and called
                and " scatter(" in bodies.get(called.group(1), ""))

    defs = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, _, dims, op, rest = m.groups()
        defs[name] = (op, rest)
        if pool_dims & set(dims.split(",")):
            assert op in _IN_PLACE or scatter_fusion(op, rest), \
                line.strip()[:300]
    kernels = [(n, rest) for n, (op, rest) in defs.items()
               if op == "custom-call" and "tpu_custom_call" in rest]
    assert len(kernels) == 1
    operands = re.findall(r"%([\w.-]+)", kernels[0][1].split(")")[0])
    for name in operands[-len(jax.tree.leaves(pools[0])) * 2:]:
        while defs[name][0] == "bitcast":
            name = re.findall(r"%([\w.-]+)", defs[name][1])[0]
        op, rest = defs[name]
        assert op in ("parameter", "get-tuple-element") \
            or scatter_fusion(op, rest), (name, op)


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
