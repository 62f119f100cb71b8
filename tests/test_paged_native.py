"""Zero-gather paged decode: the attention families' paged-NATIVE
decode/chunk steps must be bit-identical to the dense-gather oracle (and
the dense kvcache impl) across all six families, the compiled fused step
must contain no full-pool dense KV materialization (HLO shape + XLA
cost-analysis regression), batched COW must coalesce a wave's copies into
one dispatch, and the launcher's pjit builder must produce the same
tokens under a service mesh.

``PAGED_NATIVE_EXAMPLES`` scales the hypothesis example budget (the CI
hypothesis job raises it on a fixed seed).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.allocator import ParallelPlan
from repro.core.categories import Sensitivity, TaskCategory
from repro.kernels import paged_pool
from repro.models.registry import model_api
from repro.serving.engine import GenerationRequest, ServiceRuntime

from conftest import toy_config

LAT = TaskCategory(Sensitivity.LATENCY, False)
FAMILIES = ("dense", "moe", "ssm", "hybrid", "audio", "vlm")
ATTENTION_FAMILIES = ("dense", "moe", "hybrid", "audio", "vlm")
_EXAMPLES = int(os.environ.get("PAGED_NATIVE_EXAMPLES", "6"))


def _family_cfg(family):
    over = dict(num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
                head_dim=16, d_ff=64, vocab_size=97)
    if family == "moe":
        over.update(num_experts=4, experts_per_token=2,
                    moe_capacity_factor=8.0)
    elif family in ("ssm", "hybrid"):
        over.update(ssm_state=4, ssm_headdim=16)
        if family == "hybrid":
            over.update(attn_every=1)
    elif family == "audio":
        over.update(encoder_layers=1, encoder_len=8)
    elif family == "vlm":
        over.update(prefix_len=4)
    return toy_config(family=family, **over)


_CFGS = {f: _family_cfg(f) for f in FAMILIES}
_PARAMS = {}


def _family_params(family):
    if family not in _PARAMS:
        _PARAMS[family] = model_api(_CFGS[family]).init(
            jax.random.PRNGKey(7), _CFGS[family])
    return _PARAMS[family]


def _requests(cfg, rng, n_reqs):
    reqs = []
    for i in range(n_reqs):
        plen = int(rng.integers(1, 13))
        n = int(rng.integers(1, 5))
        extras = None
        if cfg.family in ("audio", "vlm"):
            dim = cfg.encoder_len if cfg.family == "audio" else cfg.prefix_len
            extras = {"embeddings": rng.normal(
                size=(dim, cfg.d_model)).astype(np.float32)}
        reqs.append(GenerationRequest(
            rid=i, tokens=rng.integers(1, cfg.vocab_size,
                                       plen).astype(np.int32),
            max_new_tokens=n, extras=extras))
    return reqs


def _serve(cfg, params, reqs, **kw):
    rt = ServiceRuntime(cfg, params, ParallelPlan(service="t", category=LAT,
                                                  bs=kw.pop("bs", 2)),
                        max_seq_len=48, block_size=8, **kw)
    for r in reqs:
        rt.submit(r)
    return rt, {r.rid: list(r.tokens) for r in rt.drain()}


# ---------------------------------------------------------------------------
# greedy-token parity: paged-native vs dense-gather oracle vs dense impl
# ---------------------------------------------------------------------------

@settings(max_examples=_EXAMPLES, deadline=None, derandomize=True)
@given(family=st.sampled_from(FAMILIES), seed=st.integers(0, 2 ** 16),
       bs=st.integers(1, 3))
def test_paged_native_matches_oracle_across_families(family, seed, bs):
    """Random admit/chunk/evict schedules must yield IDENTICAL greedy
    tokens whether attention reads K/V in place through the block tables
    (paged-native), through the dense-gather oracle step
    (``paged_native=False``), or via the dense kvcache impl — for every
    model family (pure-SSM families exercise the unchanged state path)."""
    cfg, params = _CFGS[family], _family_params(family)
    rng = np.random.default_rng(seed)
    reqs = _requests(cfg, rng, n_reqs=4)
    rt_n, native = _serve(cfg, params, reqs, bs=bs, kvcache_impl="paged")
    _, oracle = _serve(cfg, params, reqs, bs=bs, kvcache_impl="paged",
                       paged_native=False)
    _, dense = _serve(cfg, params, reqs, bs=bs, kvcache_impl="dense")
    assert native == oracle, (family, seed)
    assert native == dense, (family, seed)
    assert rt_n.paged_native == (family in ATTENTION_FAMILIES)
    assert rt_n.decode_traces <= 1           # still one compile per service


@pytest.mark.parametrize("family", ATTENTION_FAMILIES)
def test_decode_step_paged_chains_like_decode_step(family):
    """Model-level harness (no engine): after identical prefills, chaining
    ``decode_step_paged`` over the arena pools produces the same greedy
    tokens as ``decode_step`` over the dense cache."""
    from repro.serving.arena import KVArena

    cfg, params = _CFGS[family], _family_params(family)
    api = model_api(cfg)
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, cfg.vocab_size, 7).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompt[None])}
    if cfg.family in ("audio", "vlm"):
        dim = cfg.encoder_len if cfg.family == "audio" else cfg.prefix_len
        batch["embeddings"] = jnp.asarray(
            rng.normal(size=(1, dim, cfg.d_model)), jnp.float32)
    extra = cfg.prefix_len if cfg.family == "vlm" else 0

    arena = KVArena(cfg, api.init_cache, capacity=2, max_seq_len=32,
                    block_size=8)
    logits, cache = api.prefill(params, cfg, batch,
                                cache_size=arena.slot_tokens - extra)
    slot = arena.alloc(arena.slot_tokens)
    arena.write_prefill(slot, cache, prompt_len=len(prompt) + extra)
    # dense reference cache: same prefill, per-slot lens
    dense_cache = jax.tree.map(lambda x: x, cache)

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    tok_paged = tok
    live = jnp.asarray(np.arange(arena.capacity) == slot)
    for _ in range(4):
        l1, dense_cache = api.decode_step(params, cfg, tok, dense_cache)
        tokens = jnp.zeros((arena.capacity,), jnp.int32
                           ).at[slot].set(tok_paged[0])
        paged = arena.assemble(arena.pages, arena.state, arena.lens)
        l2, new_cache = api.decode_step_paged(
            params, cfg, tokens, paged, arena.device_block_tables(), live,
            block_size=arena.block_size)
        new_pages, new_state = arena.disassemble(new_cache)
        arena.pages = new_pages
        arena.state = arena.merge_state(arena.state, new_state, live)
        arena.lens = jnp.where(live, arena.lens + 1, arena.lens)
        tok = jnp.argmax(l1, -1).astype(jnp.int32)
        tok_paged = jnp.argmax(l2[slot][None], -1).astype(jnp.int32)
        assert int(tok[0]) == int(tok_paged[0]), family


# ---------------------------------------------------------------------------
# HLO regression: no full-pool dense KV materialization on the hot path
# ---------------------------------------------------------------------------

def _decode_artifacts(cfg, params, *, native, max_seq_len=256, bs=4):
    rt = ServiceRuntime(cfg, params,
                        ParallelPlan(service="t", category=LAT, bs=bs),
                        kvcache_impl="paged", max_seq_len=max_seq_len,
                        block_size=32, paged_native=native)
    rt.submit(GenerationRequest(rid=0,
                                tokens=np.arange(1, 6, dtype=np.int32),
                                max_new_tokens=2))
    rt.drain()
    arena = rt.groups[0].arena
    lowered = jax.jit(rt._paged_decode_pure(arena)).lower(
        rt.params, jnp.zeros((arena.capacity,), jnp.int32),
        arena.pages, arena.state, arena.lens,
        jnp.ones((arena.capacity,), bool), arena.device_block_tables())
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    return rt, arena, compiled.as_text(), dict(cost)


def test_paged_decode_step_contains_no_full_pool_gather(dense_cfg):
    """The compiled paged-native decode step must never materialize the
    ``(layers, capacity, slot_tokens, Hkv, D)`` dense KV view the old
    gather path round-tripped per token — asserted on the optimized HLO
    (the full-view shape is absent) AND on XLA's cost analysis (bytes
    accessed strictly below the dense-gather oracle's; on TPU the Pallas
    kernels additionally skip past-``len`` blocks, so real traffic scales
    with live tokens)."""
    params = model_api(dense_cfg).init(jax.random.PRNGKey(0), dense_cfg)
    rt_n, arena, hlo_n, cost_n = _decode_artifacts(dense_cfg, params,
                                                   native=True)
    rt_o, _, hlo_o, cost_o = _decode_artifacts(dense_cfg, params,
                                               native=False)
    full_view = (f"[{dense_cfg.num_layers},{arena.capacity},"
                 f"{arena.slot_tokens},{dense_cfg.num_kv_heads},"
                 f"{dense_cfg.head_dim}]")
    assert full_view not in hlo_n, \
        f"paged-native decode step materializes a full dense view " \
        f"{full_view}"
    assert full_view in hlo_o        # the oracle really is the old path
    assert cost_n["bytes accessed"] < cost_o["bytes accessed"]


def test_paged_decode_bytes_grow_slower_than_pool(dense_cfg):
    """Doubling the per-slot token budget grows the dense-gather oracle's
    bytes-accessed by the full pool delta several times over (gather +
    re-scatter round trips); the paged-native step's growth must stay
    well below the oracle's — the per-token bandwidth win the tentpole
    exists for."""
    params = model_api(dense_cfg).init(jax.random.PRNGKey(0), dense_cfg)

    def bytes_at(native, msl):
        _, _, _, cost = _decode_artifacts(dense_cfg, params, native=native,
                                          max_seq_len=msl)
        return cost["bytes accessed"]

    d_native = bytes_at(True, 512) - bytes_at(True, 128)
    d_oracle = bytes_at(False, 512) - bytes_at(False, 128)
    assert d_native < 0.75 * d_oracle, (d_native, d_oracle)


def test_decode_cost_analysis_keeps_compile_counters(dense_cfg):
    params = model_api(dense_cfg).init(jax.random.PRNGKey(0), dense_cfg)
    rt = ServiceRuntime(dense_cfg, params,
                        ParallelPlan(service="t", category=LAT, bs=2),
                        kvcache_impl="paged", max_seq_len=64, block_size=8)
    rt.submit(GenerationRequest(rid=0,
                                tokens=np.arange(1, 6, dtype=np.int32),
                                max_new_tokens=2))
    rt.drain()
    traces = rt.decode_traces
    cost = rt.decode_cost_analysis()
    assert cost.get("bytes accessed", 0) > 0
    assert rt.decode_traces == traces      # throwaway lowering, no drift


# ---------------------------------------------------------------------------
# kernels: ref fallback's length-clipped gather stays bit-identical
# ---------------------------------------------------------------------------

def _stored(rng, P, bs, Hkv, D):
    """A random natural (P, bs, Hkv, D) pool and the same pool as one
    layer of the arena's stored layout."""
    nat = jnp.asarray(rng.normal(size=(P, bs, Hkv, D)).astype(np.float32))
    return nat, paged_pool.from_natural(nat[None])


def test_paged_decode_ref_masked_gather_bit_identical(rng):
    """ops.paged_decode_attention's ref fallback clips the block table to
    per-slot up-to-len rows (past-len entries read the one trash page).
    The clip must be invisible to the math: bit-identical to the oracle
    on the UNCLIPPED gather."""
    from repro.kernels import ops, ref
    B, Hq, Hkv, D, bs, nblk, P = 3, 4, 2, 16, 8, 4, 14
    q = jnp.asarray(rng.normal(size=(B, Hq, D)).astype(np.float32))
    kn, kp = _stored(rng, P, bs, Hkv, D)
    vn, vp = _stored(rng, P, bs, Hkv, D)
    bt = jnp.asarray(rng.permutation(P - 1)[:B * nblk]
                     .reshape(B, nblk).astype(np.int32))
    lens = jnp.asarray(np.array([3, 17, 32], np.int32))
    gather = lambda p: p[bt].reshape(B, nblk * bs, Hkv, D)
    want = ref.decode_attention_ref(q, gather(kn), gather(vn), lens)
    got = ops.paged_decode_attention(q, kp, vp, bt, lens, kv_heads=Hkv,
                                     impl="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_chunk_ref_masked_gather_bit_identical(rng):
    from repro.kernels import ops, ref
    B, T, Hq, Hkv, D, bs, nblk, P = 2, 8, 4, 2, 16, 8, 4, 10
    q = jnp.asarray(rng.normal(size=(B, T, Hq, D)).astype(np.float32))
    kn, kp = _stored(rng, P, bs, Hkv, D)
    vn, vp = _stored(rng, P, bs, Hkv, D)
    bt = jnp.asarray(rng.permutation(P - 1)[:B * nblk]
                     .reshape(B, nblk).astype(np.int32))
    start = jnp.asarray(np.array([4, 19], np.int32))
    cl = jnp.asarray(np.array([8, 6], np.int32))
    gather = lambda p: p[bt].reshape(B, nblk * bs, Hkv, D)
    want = ref.chunk_attention_ref(q, gather(kn), gather(vn), start, cl)
    got = ops.paged_chunk_attention(q, kp, vp, bt, start, cl, kv_heads=Hkv,
                                    impl="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# (Hq, Hkv, D), the KV heads the stored layout packs into a row, and the
# kernel's tile budget (query rows x lanes; None keeps the default)
_GEOMETRIES = {
    "D64-G2": ((4, 4, 64), 2, None),     # minicpm-2b's 64-lane heads
    "D128-G1": ((4, 2, 128), 1, None),   # heads as wide as a row
    "D64-GQA": ((8, 2, 64), 2, None),    # 4 query heads per KV head
    "D64-odd": ((3, 3, 64), 3, None),    # 3 heads in a row of 256 lanes
    "D112-G8": ((8, 8, 112), 8, None),   # zamba2-7b's heads, 896 lanes
    "D112-pad": ((4, 2, 112), 1, None),  # one head, 16 padding lanes
    "D64-GQA-split": ((8, 2, 64), 2, 1024),  # a group's heads in 8 tiles
    "D16-GQA": ((4, 2, 16), 2, None),    # 2 narrow heads, 96 padding lanes
}


@pytest.mark.parametrize("geometry", list(_GEOMETRIES))
@pytest.mark.parametrize("kv", ["float", "int8"])
@pytest.mark.parametrize("phase", ["decode", "chunk"])
def test_paged_kernels_read_stored_layout(phase, kv, geometry, monkeypatch):
    """The paged kernels (interpret mode) read the arena's stored layout
    — head groups packed into lane rows (padding lanes where a row is not
    whole tiles), int8 scales in folded rows, one layer of a stacked pool
    through its layer index, a group's query heads in one tile or split
    over several — and agree with the ref path on the same pools and
    with the oracle on the natural-layout gather of the same
    (dequantized) values."""
    from repro.kernels import decode_attention, ops, ref
    from repro.kernels.quant import dequantize, quantize
    (Hq, Hkv, D), G, tile = _GEOMETRIES[geometry]
    if tile is not None:
        monkeypatch.setattr(decode_attention, "_TILE_ELEMS", tile)
    assert paged_pool.head_group(D, Hkv) == G
    B, bs, nblk, L, layer = 3, 8, 4, 2, 1
    P = B * nblk + 1
    rng = np.random.default_rng(sorted(_GEOMETRIES).index(geometry))
    nat = [jnp.asarray(rng.normal(size=(L, P, bs, Hkv, D)), jnp.float32)
           for _ in range(2)]
    quant = kv == "int8"
    kp, vp = (paged_pool.from_natural(n, quantized=quant) for n in nat)
    W = paged_pool.row_lanes(G, D)
    assert W % 128 == 0 and W - G * D < 128
    assert paged_pool.values_of(kp).shape == (L, Hkv // G, P, bs, W)
    if quant:
        nat = [dequantize(*quantize(n)) for n in nat]
    bt = jnp.asarray(rng.permutation(P - 1).reshape(B, nblk)
                     .astype(np.int32))
    gather = lambda n: n[layer][bt].reshape(B, nblk * bs, Hkv, D)
    if phase == "decode":
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        lens = jnp.asarray([1, 17, nblk * bs], jnp.int32)
        want = ref.decode_attention_ref(q, *map(gather, nat), lens)
        run = lambda impl: ops.paged_decode_attention(
            q, kp, vp, bt, lens, layer=layer, kv_heads=Hkv, impl=impl)
    else:
        q = jnp.asarray(rng.normal(size=(B, 5, Hq, D)), jnp.float32)
        start = jnp.asarray([0, 9, nblk * bs - 5], jnp.int32)
        cl = jnp.asarray([5, 3, 5], jnp.int32)
        want = ref.chunk_attention_ref(q, *map(gather, nat), start, cl)
        run = lambda impl: ops.paged_chunk_attention(
            q, kp, vp, bt, start, cl, layer=layer, kv_heads=Hkv,
            impl=impl)
    np.testing.assert_allclose(np.asarray(run("ref")), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(run("pallas_interpret")),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_paged_native_pallas_interpret_matches_ref():
    """The fused engine path under impl='pallas_interpret' (the scalar-
    prefetch block-table kernels) must produce the ref path's greedy
    tokens — the CI stand-in for the real-TPU bit-exactness gate."""
    cfg = _CFGS["dense"]
    params = _family_params("dense")
    rng = np.random.default_rng(3)
    reqs = _requests(cfg, rng, n_reqs=3)
    _, ref_toks = _serve(cfg, params, reqs, bs=2, kvcache_impl="paged",
                         impl="ref")
    _, pallas_toks = _serve(cfg, params, reqs, bs=2, kvcache_impl="paged",
                            impl="pallas_interpret")
    assert ref_toks == pallas_toks


# ---------------------------------------------------------------------------
# gating and validation
# ---------------------------------------------------------------------------

def test_paged_native_gating():
    """Pure-SSM families and ring (sliding-window) layouts keep the
    dense-view/state path; forcing paged_native there must fail loudly."""
    cfg = _CFGS["ssm"]
    params = _family_params("ssm")
    rt = ServiceRuntime(cfg, params,
                        ParallelPlan(service="t", category=LAT, bs=2),
                        kvcache_impl="paged", max_seq_len=48, block_size=8)
    assert not rt.paged_native
    with pytest.raises(ValueError):
        ServiceRuntime(cfg, params,
                       ParallelPlan(service="t", category=LAT, bs=2),
                       kvcache_impl="paged", max_seq_len=48, block_size=8,
                       paged_native=True)
    ring_cfg = toy_config(sliding_window=16)     # < 48-token slot budget
    ring_params = model_api(ring_cfg).init(jax.random.PRNGKey(0), ring_cfg)
    rt = ServiceRuntime(ring_cfg, ring_params,
                        ParallelPlan(service="t", category=LAT, bs=2),
                        kvcache_impl="paged", max_seq_len=48, block_size=8)
    assert not rt.paged_native and rt.ring_fallback


# ---------------------------------------------------------------------------
# batched COW (PR 4 follow-up satellite)
# ---------------------------------------------------------------------------

def test_cow_blocks_batches_one_dispatch(dense_cfg):
    """Several divergence COWs coalesce into ONE jitted scatter: contents
    copied faithfully, refcounts correct, exactly one dispatch counted."""
    from repro.models import transformer as T
    from repro.serving.arena import KVArena

    params = T.init(jax.random.PRNGKey(0), dense_cfg)
    arena = KVArena(dense_cfg, T.init_cache, capacity=3, max_seq_len=32,
                    block_size=8)
    prompt = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])
    _, cache = T.prefill(params, dense_cfg, {"tokens": prompt},
                         cache_size=arena.slot_tokens)
    owner = arena.alloc(32)
    arena.write_prefill(owner, cache, prompt_len=16)
    shared = list(arena.block_tables()[owner][:2])
    s1 = arena.alloc(32, shared=shared)
    s2 = arena.alloc(32, shared=shared)
    before = arena.dense_view(arena.pages,
                              jnp.asarray(arena.block_tables()))
    copied = arena.cow_blocks([(s1, 0), (s1, 1), (s2, 0)])
    assert copied == 3
    assert arena.cow_calls == 1              # one dispatch for the wave
    after = arena.dense_view(arena.pages, jnp.asarray(arena.block_tables()))
    for b, a in zip(before, after):          # copies are faithful and the
        np.testing.assert_array_equal(       # owner's rows untouched
            np.asarray(b[:, [owner, s1, s2], :16]),
            np.asarray(a[:, [owner, s1, s2], :16]))
    # the three sharers now own private physical blocks
    bt = arena.block_tables()
    assert bt[s1][0] != bt[owner][0] and bt[s2][0] != bt[owner][0]
    assert bt[s1][0] != bt[s2][0]
    assert arena.block_ref(int(bt[owner][0])) == 1


def test_cow_blocks_exhaustion_leaves_state_consistent(dense_cfg):
    """When the pool cannot supply every destination, cow_blocks must
    raise BEFORE mutating anything: no pair may be left pointing at a
    claimed-but-never-copied block (destinations are claimed up front,
    bookkeeping mutates only after the claim succeeds)."""
    from repro.models import transformer as T
    from repro.serving.arena import KVArena

    params = T.init(jax.random.PRNGKey(0), dense_cfg)
    arena = KVArena(dense_cfg, T.init_cache, capacity=2, max_seq_len=32,
                    block_size=8, pool_blocks=6)
    prompt = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])
    _, cache = T.prefill(params, dense_cfg, {"tokens": prompt},
                         cache_size=arena.slot_tokens)
    owner = arena.alloc(32)
    arena.write_prefill(owner, cache, prompt_len=16)
    shared = list(arena.block_tables()[owner][:2])
    sharer = arena.alloc(32, shared=shared)      # pool now exhausted
    bt_before = arena.block_tables().copy()
    refs_before = [arena.block_ref(int(b)) for b in shared]
    with pytest.raises(RuntimeError):
        arena.cow_blocks([(sharer, 0), (sharer, 1)])
    np.testing.assert_array_equal(arena.block_tables(), bt_before)
    assert [arena.block_ref(int(b)) for b in shared] == refs_before
    assert arena.cow_copies == 0 and arena.cow_calls == 0


def test_admission_wave_cows_coalesce(dense_cfg):
    """Engine satellite: a wave of admissions sharing one template's
    partial tail must flush its divergence COWs as one batched dispatch
    (arena.cow_calls grows by ~1 per wave, not per admission)."""
    from repro.core.categories import Sensitivity, TaskCategory
    from repro.models import transformer as T

    params = T.init(jax.random.PRNGKey(0), dense_cfg)
    plan = ParallelPlan(service="t",
                        category=TaskCategory(Sensitivity.FREQUENCY, False),
                        bs=4)
    rt = ServiceRuntime(dense_cfg, params, plan, kvcache_impl="paged",
                        max_seq_len=96, block_size=8)
    rng = np.random.default_rng(2)
    template = rng.integers(1, 257, 20).astype(np.int32)  # 2 full + partial
    rt.submit(GenerationRequest(rid=0, tokens=template, max_new_tokens=2))
    rt.drain()                                # template indexed on eviction
    arena = rt.groups[0].arena
    calls0, copies0 = arena.cow_calls, arena.cow_copies
    for i in range(1, 4):                     # one wave of partial-tail hits
        rt.submit(GenerationRequest(
            rid=i, tokens=np.concatenate(
                [template, rng.integers(1, 257, 4).astype(np.int32)]),
            max_new_tokens=2))
    rt.drain()
    new_copies = arena.cow_copies - copies0
    assert new_copies >= 2                    # the wave really did COW
    assert arena.cow_calls - calls0 < new_copies  # ...in fewer dispatches


def test_chunk_write_bytes_not_counted_as_admission_copies(dense_cfg):
    """Satellite fix: _run_chunk's appends land in chunk_write_bytes, so a
    pure chunked-admission run reports ZERO admission-copy bytes."""
    from repro.models import transformer as T
    params = T.init(jax.random.PRNGKey(0), dense_cfg)
    rt = ServiceRuntime(dense_cfg, params,
                        ParallelPlan(service="t", category=LAT, bs=2),
                        kvcache_impl="paged", max_seq_len=64, block_size=8)
    rt.submit(GenerationRequest(rid=0,
                                tokens=np.arange(1, 40, dtype=np.int32),
                                max_new_tokens=2))
    rt.drain()
    assert rt.admission_copy_bytes == 0
    assert rt.chunk_write_bytes > 0


# ---------------------------------------------------------------------------
# launcher: pjit'd paged decode under a service mesh
# ---------------------------------------------------------------------------

def test_pjit_paged_decode_builder_matches_local_jit():
    """Serving under a (data, model) service mesh — arena born with the
    mesh's placements, every arena step jitted under it — must produce
    the same greedy tokens as the one-device engine, still with exactly
    one decode compile (the buffers keep their shardings across calls)."""
    from repro.launch import mesh as meshlib

    cfg = _CFGS["dense"]
    params = _family_params("dense")
    rng = np.random.default_rng(9)
    reqs = _requests(cfg, rng, n_reqs=3)
    mesh = meshlib.make_mesh((1, jax.device_count()), ("data", "model"))
    rt_m, mesh_toks = _serve(cfg, params, reqs, bs=2, kvcache_impl="paged",
                             mesh=mesh)
    _, local_toks = _serve(cfg, params, reqs, bs=2, kvcache_impl="paged")
    assert mesh_toks == local_toks
    assert rt_m.decode_traces == 1
    arena = rt_m.groups[0].arena
    for buf, sh in zip(jax.tree.leaves((arena.pages, arena.state,
                                        arena.lens)),
                       jax.tree.leaves(arena.shardings())):
        assert buf.sharding == sh and sh.mesh == mesh
