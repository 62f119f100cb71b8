"""Quantized paged KV: int8 block pools with per-row scales.

Covers the quantize→dequant math (error bound, exact ref↔Pallas-interpret
kernel parity through ``ops`` with ``QuantPages`` pools), end-to-end
tolerance of quantized-native serving against the unquantized oracle
across the attention families, prefix-cache share/COW/evict interleavings
over quantized blocks (no cross-slot corruption, identical tokens with
the cache on vs off), and the precision-knob plumbing (``ParallelPlan``
validation, category-derived defaults, engine and launcher rejection of
int8 on the dense cache impl).

``QUANT_KV_EXAMPLES`` scales the property-test budget (the CI hypothesis
job raises it on a fixed seed)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.allocator import ParallelPlan
from repro.core.categories import (KV_DTYPE_BY_SENSITIVITY, Sensitivity,
                                   TaskCategory)
from repro.kernels import ops, paged_pool
from repro.kernels.quant import QuantPages, dequantize, quantize
from repro.models.registry import model_api
from repro.serving.arena import KVArena
from repro.serving.engine import GenerationRequest, ServiceRuntime

from conftest import toy_config

LAT = TaskCategory(Sensitivity.LATENCY, False)
FREQ = TaskCategory(Sensitivity.FREQUENCY, False)
ATTENTION_FAMILIES = ("dense", "moe", "hybrid", "audio", "vlm")
_EXAMPLES = int(os.environ.get("QUANT_KV_EXAMPLES", "6"))


def _family_cfg(family):
    over = dict(num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
                head_dim=16, d_ff=64, vocab_size=97)
    if family == "moe":
        over.update(num_experts=4, experts_per_token=2,
                    moe_capacity_factor=8.0)
    elif family == "hybrid":
        over.update(ssm_state=4, ssm_headdim=16, attn_every=1)
    elif family == "audio":
        over.update(encoder_layers=1, encoder_len=8)
    elif family == "vlm":
        over.update(prefix_len=4)
    return toy_config(family=family, **over)


_CFGS = {f: _family_cfg(f) for f in ATTENTION_FAMILIES}
_PARAMS = {}


def _family_params(family):
    if family not in _PARAMS:
        _PARAMS[family] = model_api(_CFGS[family]).init(
            jax.random.PRNGKey(7), _CFGS[family])
    return _PARAMS[family]


def _requests(cfg, rng, n_reqs, max_new=4):
    reqs = []
    for i in range(n_reqs):
        plen = int(rng.integers(1, 13))
        extras = None
        if cfg.family in ("audio", "vlm"):
            dim = cfg.encoder_len if cfg.family == "audio" else cfg.prefix_len
            extras = {"embeddings": rng.normal(
                size=(dim, cfg.d_model)).astype(np.float32)}
        reqs.append(GenerationRequest(
            rid=i, tokens=rng.integers(1, cfg.vocab_size,
                                       plen).astype(np.int32),
            max_new_tokens=max_new, extras=extras))
    return reqs


def _serve(cfg, params, reqs, kv_dtype, **kw):
    plan = ParallelPlan(service="t", category=LAT, bs=kw.pop("bs", 2),
                        kv_dtype=kv_dtype)
    rt = ServiceRuntime(cfg, params, plan, max_seq_len=48, block_size=8,
                        kvcache_impl="paged", **kw)
    for r in reqs:
        rt.submit(r)
    return rt, {r.rid: list(r.tokens) for r in rt.drain()}


# ---------------------------------------------------------------------------
# quantize / dequantize math
# ---------------------------------------------------------------------------

@settings(max_examples=_EXAMPLES, deadline=None, derandomize=True)
@given(seed=st.integers(0, 2 ** 16), rows=st.integers(1, 16),
       d=st.sampled_from((4, 16, 64)), scale=st.sampled_from((0.1, 1.0, 8.0)))
def test_quantize_roundtrip_error_is_bounded_by_half_step(seed, rows, d,
                                                          scale):
    """Symmetric per-row int8: every element's roundtrip error is at most
    half a quantization step (rowmax/127/2) plus float fuzz, and the zero
    row survives the EPS floor without NaN/Inf."""
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(rows, d)) * scale).astype(np.float32)
    x[0] = 0.0
    q, s = quantize(jnp.asarray(x))
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == (rows,)
    back = np.asarray(dequantize(q, s))
    step = np.maximum(np.abs(x).max(axis=-1, keepdims=True), 1e-8) / 127.0
    assert np.all(np.isfinite(back))
    assert np.all(np.abs(back - x) < 0.5 * step + 1e-6)


def test_quant_pages_is_a_transparent_pytree():
    """QuantPages flattens to (values, scales) so jit/scan/donation see
    two leaves, while shape/dtype proxy the value array for the families'
    shape-reading call sites."""
    qp = QuantPages(*quantize(jnp.ones((3, 4, 2, 8))))
    leaves, treedef = jax.tree.flatten(qp)
    assert len(leaves) == 2
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert isinstance(rebuilt, QuantPages)
    assert qp.shape == (3, 4, 2, 8) and qp.ndim == 4
    assert qp.dtype == jnp.int8


# ---------------------------------------------------------------------------
# kernel parity: quantized ref vs Pallas interpret through ops dispatch
# ---------------------------------------------------------------------------

def _paged_fixture(seed, B=2, blocks=4, bs=8, Hq=4, Hkv=2, D=16):
    """Float pools in the arena's stored layout (one layer), the same
    pools as int8 ``QuantPages``, a block table and lengths."""
    rng = np.random.default_rng(seed)
    P = B * blocks + 1                                    # + trash page
    nat = [jnp.asarray(rng.normal(size=(1, P, bs, Hkv, D)), jnp.float32)
           for _ in range(2)]
    kp, vp = (paged_pool.from_natural(n) for n in nat)
    kq, vq = (paged_pool.from_natural(n, quantized=True) for n in nat)
    bt = jnp.arange(B * blocks, dtype=jnp.int32).reshape(B, blocks)
    lens = jnp.asarray(rng.integers(1, blocks * bs + 1, B), jnp.int32)
    return (kp, vp), (kq, vq), bt, lens, (Hq, Hkv, D)


@settings(max_examples=_EXAMPLES, deadline=None, derandomize=True)
@given(seed=st.integers(0, 2 ** 16))
def test_quant_paged_decode_interpret_matches_ref(seed):
    """The fused dequant decode kernel (interpret mode) must reproduce the
    ref path's gather→dequant→oracle to float fuzz: both consume the SAME
    int8 values + f32 scales, so any gap is kernel logic, not rounding."""
    _, (kq, vq), bt, lens, (Hq, Hkv, D) = _paged_fixture(seed)
    q = jnp.asarray(np.random.default_rng(seed + 1).normal(
        size=(bt.shape[0], Hq, D)), jnp.float32)
    out_ref = ops.paged_decode_attention(q, kq, vq, bt, lens, kv_heads=Hkv,
                                         impl="ref")
    out_pl = ops.paged_decode_attention(q, kq, vq, bt, lens, kv_heads=Hkv,
                                        impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=_EXAMPLES, deadline=None, derandomize=True)
@given(seed=st.integers(0, 2 ** 16), chunk=st.sampled_from((1, 4, 8)))
def test_quant_paged_chunk_interpret_matches_ref(seed, chunk):
    """Quantized chunked-prefill: same exact-parity contract as decode,
    with per-slot start offsets and causal masking inside the chunk."""
    _, (kq, vq), bt, lens, (Hq, Hkv, D) = _paged_fixture(seed)
    B = bt.shape[0]
    rng = np.random.default_rng(seed + 2)
    bs = paged_pool.block_size_of(kq)
    start = jnp.asarray([int(l) for l in np.minimum(
        np.asarray(lens), bt.shape[1] * bs - chunk)], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, chunk, Hq, D)), jnp.float32)
    cl = jnp.full((B,), chunk, jnp.int32)
    out_ref = ops.paged_chunk_attention(q, kq, vq, bt, start, cl,
                                        kv_heads=Hkv, impl="ref")
    out_pl = ops.paged_chunk_attention(q, kq, vq, bt, start, cl,
                                       kv_heads=Hkv, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=_EXAMPLES, deadline=None, derandomize=True)
@given(seed=st.integers(0, 2 ** 16))
def test_quantized_decode_tracks_unquantized_oracle(seed):
    """int8 pools vs the same pools unquantized: attention output drifts
    only by the quantization noise (unit-normal K/V → well under 5e-2),
    never structurally (wrong rows / dropped blocks would blow this up)."""
    (kp, vp), (kq, vq), bt, lens, (Hq, Hkv, D) = _paged_fixture(seed)
    q = jnp.asarray(np.random.default_rng(seed + 3).normal(
        size=(bt.shape[0], Hq, D)), jnp.float32)
    exact = ops.paged_decode_attention(q, kp, vp, bt, lens, kv_heads=Hkv,
                                       impl="ref")
    approx = ops.paged_decode_attention(q, kq, vq, bt, lens, kv_heads=Hkv,
                                        impl="ref")
    np.testing.assert_allclose(np.asarray(approx), np.asarray(exact),
                               atol=5e-2)


# ---------------------------------------------------------------------------
# family-level parity: quantized native serving vs bf16 within tolerance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ATTENTION_FAMILIES)
def test_families_quantized_serving_tracks_native_tokens(family):
    """Serving an identical request wave with ``kv_dtype='int8'`` must
    produce the same response lengths and near-identical greedy tokens as
    the native-precision run (small drift may flip a late token; gross
    disagreement means the quantized write or read path is broken) — with
    still exactly one decode compile."""
    cfg, params = _CFGS[family], _family_params(family)
    rng = np.random.default_rng(13)
    reqs = _requests(cfg, rng, n_reqs=4)
    rt_q, toks_q = _serve(cfg, params, reqs, kv_dtype="int8")
    _, toks_n = _serve(cfg, params, reqs, kv_dtype="bf16")
    assert rt_q.kv_dtype == "int8"
    assert rt_q.decode_traces <= 1
    assert set(toks_q) == set(toks_n)
    agree = total = 0
    for rid, seq in toks_n.items():
        assert len(toks_q[rid]) == len(seq)
        agree += sum(a == b for a, b in zip(toks_q[rid], seq))
        total += len(seq)
    assert agree >= 0.9 * total, (family, toks_q, toks_n)


# ---------------------------------------------------------------------------
# prefix cache over quantized blocks: share / COW / evict interleavings
# ---------------------------------------------------------------------------

_QCFG = toy_config(num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
                   head_dim=16, d_ff=64)
_QPARAMS = None


def _qparams():
    global _QPARAMS
    if _QPARAMS is None:
        _QPARAMS = model_api(_QCFG).init(jax.random.PRNGKey(7), _QCFG)
    return _QPARAMS


def _qarena(capacity=3, **kw):
    return KVArena(_QCFG, model_api(_QCFG).init_cache, capacity=capacity,
                   max_seq_len=32, block_size=8, kv_dtype="int8", **kw)


def test_quantized_share_cow_evict_interleaving_preserves_other_slots():
    """Over int8 pools: share a 2-block prefix, COW-fork the sharer, write
    divergent rows into the fork, evict the source — the surviving chain
    still dequantizes to the original prefix bit-for-bit (COW clones the
    int8 values AND their scales), and every block returns to the free
    list at the end."""
    api = model_api(_QCFG)
    a = _qarena()
    assert isinstance(a.pages[0], QuantPages)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, _QCFG.vocab_size, 16).astype(np.int32)
    _, cache = api.prefill(_qparams(), _QCFG, {"tokens": prompt[None]},
                           cache_size=a.slot_tokens)
    sA = a.alloc(24)
    a.write_prefill(sA, cache, prompt_len=16)
    rowA = a.block_tables()[sA][:2]
    want = np.asarray(
        a.dense_view(a.pages, a.block_tables()[sA][None])[0])[:, :, :16]
    # share, then COW-fork block 0 of the sharer
    sB = a.alloc(24, shared=list(rowA))
    assert all(a.block_ref(int(b)) == 2 for b in rowA)
    assert a.cow_block(sB, 0)
    rowB_full = a.block_tables()[sB][None]
    got_fork = np.asarray(a.dense_view(a.pages, rowB_full)[0])[:, :, :8]
    np.testing.assert_allclose(got_fork, want[:, :, :8])   # exact clone
    # divergent writes into the fork must not leak into A's chain
    dense_new = [jnp.ones((leaf.shape[0], 1, a.slot_tokens,
                           *leaf.shape[3:]), jnp.float32)
                 for leaf in (cache["k"], cache["v"])]
    a.pages = a.append_rows(a.pages, dense_new, jnp.zeros((1,), jnp.int32),
                            jnp.ones((1,), bool), jnp.asarray(rowB_full))
    rowA_full = a.block_tables()[sA][None]
    va = np.asarray(a.dense_view(a.pages, rowA_full)[0])[:, :, :16]
    np.testing.assert_allclose(va, want)
    # evict the source: the fork's surviving shared block keeps the data
    a.free(sA)
    assert a.block_ref(int(rowA[1])) == 1
    vb = np.asarray(a.dense_view(a.pages, a.block_tables()[sB][None])[0])
    np.testing.assert_allclose(vb[:, :, 8:16], want[:, :, 8:16])
    a.free(sB)
    assert len(a._free_blocks) == a.pool_blocks


def test_quantized_prefix_cache_tokens_match_cache_off_run():
    """Engine-level: with int8 pools, warm template + sharing wave +
    mid-block divergence (forcing COW on a quantized block) produce
    IDENTICAL tokens to a cache-off int8 run, with real hit/COW
    telemetry — sharing reuses int8 blocks, it never re-quantizes."""
    rng = np.random.default_rng(3)
    base = rng.integers(1, _QCFG.vocab_size, 20).astype(np.int32)

    def run(**kw):
        plan = ParallelPlan(service="t", category=LAT, bs=2,
                            kv_dtype="int8")
        rt = ServiceRuntime(_QCFG, _qparams(), plan, max_seq_len=64,
                            block_size=8, kvcache_impl="paged", **kw)
        reqs = [GenerationRequest(rid=0, tokens=base, max_new_tokens=3)]
        for r in reqs:
            rt.submit(r)
        toks = {r.rid: tuple(r.tokens) for r in rt.drain()}
        wave = [GenerationRequest(
            rid=1, tokens=np.concatenate([base[:18], [88, 87]])
            .astype(np.int32), max_new_tokens=3),
            GenerationRequest(rid=2, tokens=base.copy(), max_new_tokens=3)]
        for r in wave:
            rt.submit(r)
        toks.update({r.rid: tuple(r.tokens) for r in rt.drain()})
        return rt, toks

    rt_on, toks_on = run()
    rt_off, toks_off = run(prefix_cache=0)
    assert rt_on.kv_dtype == "int8" and rt_off.kv_dtype == "int8"
    assert toks_on == toks_off
    assert rt_on.prefix_hits >= 1
    assert rt_on.prefix_cow_copies >= 1
    assert rt_on.prefill_tokens_computed < rt_off.prefill_tokens_computed


# ---------------------------------------------------------------------------
# precision-knob plumbing: plan validation, category defaults, launcher
# ---------------------------------------------------------------------------

def test_parallel_plan_rejects_unknown_kv_dtype():
    with pytest.raises(ValueError, match="kv_dtype"):
        ParallelPlan(service="t", category=LAT, bs=1, kv_dtype="fp8")
    with pytest.raises(ValueError, match="kv_dtype"):
        ParallelPlan(service="t", category=LAT, bs=1, kv_dtype="float16")


def test_resolved_kv_dtype_follows_category_then_override():
    assert ParallelPlan(service="t", category=LAT,
                        bs=1).resolved_kv_dtype() == "bf16"
    assert ParallelPlan(service="t", category=FREQ,
                        bs=1).resolved_kv_dtype() == "int8"
    assert ParallelPlan(service="t", category=FREQ, bs=1,
                        kv_dtype="bf16").resolved_kv_dtype() == "bf16"
    assert ParallelPlan(service="t", category=LAT, bs=1,
                        kv_dtype="int8").resolved_kv_dtype() == "int8"
    assert set(KV_DTYPE_BY_SENSITIVITY) == {Sensitivity.LATENCY,
                                            Sensitivity.FREQUENCY}


def test_engine_rejects_explicit_int8_on_dense_cache():
    plan = ParallelPlan(service="t", category=LAT, bs=1, kv_dtype="int8")
    with pytest.raises(ValueError, match="paged"):
        ServiceRuntime(_QCFG, _qparams(), plan, max_seq_len=32,
                       block_size=8, kvcache_impl="dense")


def test_engine_category_int8_degrades_to_native_on_dense_cache():
    """A frequency plan's DERIVED int8 silently stays native on the dense
    impl (there are no page pools to quantize) — only the explicit
    override is an error."""
    plan = ParallelPlan(service="t", category=FREQ, bs=1)
    rt = ServiceRuntime(_QCFG, _qparams(), plan, max_seq_len=32,
                        block_size=8, kvcache_impl="dense")
    assert rt.kv_dtype == "bf16"


def test_arena_rejects_unknown_kv_dtype():
    with pytest.raises(ValueError, match="kv_dtype"):
        KVArena(_QCFG, model_api(_QCFG).init_cache, capacity=2,
                max_seq_len=32, block_size=8, kv_dtype="fp8")


def test_serve_launcher_rejects_bad_kv_dtype_flags():
    from repro.launch import serve
    with pytest.raises(SystemExit):
        serve.main(["--archs", "codeqwen1.5-7b", "--requests", "1",
                    "--kv-dtype", "fp8"])
    with pytest.raises(SystemExit):
        serve.main(["--archs", "codeqwen1.5-7b", "--requests", "1",
                    "--kv-dtype", "int8", "--kvcache-impl", "dense"])
