"""Chunked piggybacked prefill: family-parity harness (chunked prefill
must produce the same greedy tokens as one-shot prefill across all six
model families and both kvcache impls), the chunk-attention kernels, the
arena's multi-token append, and the truthful-timing fix.

The property test drives random admit/chunk/decode schedules through the
serving engine; ``CHUNKED_PREFILL_EXAMPLES`` scales the example budget
(the CI hypothesis-profile job raises it on a fixed seed)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.allocator import ParallelPlan
from repro.core.categories import Sensitivity, TaskCategory
from repro.models.registry import model_api
from repro.serving.engine import GenerationRequest, ServiceRuntime

from conftest import toy_config

LAT = TaskCategory(Sensitivity.LATENCY, False)
FAMILIES = ("dense", "moe", "ssm", "hybrid", "audio", "vlm")
_EXAMPLES = int(os.environ.get("CHUNKED_PREFILL_EXAMPLES", "6"))


def _family_cfg(family):
    """Tiny per-family config.  MoE runs at high capacity factor: chunked
    prefill legitimately changes the routing-group granularity, so exact
    parity is only guaranteed while expert capacity is not binding."""
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
# family parity: chunked <=> one-shot, across both kvcache impls
# ---------------------------------------------------------------------------

@settings(max_examples=_EXAMPLES, deadline=None, derandomize=True)
@given(family=st.sampled_from(FAMILIES), seed=st.integers(0, 2 ** 16),
       bs=st.integers(1, 3))
def test_chunked_prefill_matches_one_shot_across_families(family, seed, bs):
    """Random admit/evict schedules with mixed prompt lengths must yield
    IDENTICAL greedy tokens whether prompts are prefilled in one shot
    (paged or dense impl) or chunk-by-chunk through the arena's block
    tables — for every model family."""
    cfg, params = _CFGS[family], _family_params(family)
    rng = np.random.default_rng(seed)
    reqs = _requests(cfg, rng, n_reqs=4)
    _, chunked = _serve(cfg, params, reqs, bs=bs, kvcache_impl="paged")
    _, oneshot = _serve(cfg, params, reqs, bs=bs, kvcache_impl="paged",
                        chunked_prefill=False)
    _, dense = _serve(cfg, params, reqs, bs=bs, kvcache_impl="dense")
    assert chunked == oneshot, (family, seed)
    assert chunked == dense, (family, seed)


@pytest.mark.parametrize("family", FAMILIES)
def test_prefill_chunk_chain_matches_prefill_logits(family):
    """Model-level harness (no engine): chaining ``prefill_chunk`` over a
    prompt reproduces one-shot ``prefill``'s final logits and its greedy
    continuation, including uneven final chunks."""
    cfg, params = _CFGS[family], _family_params(family)
    api = model_api(cfg)
    rng = np.random.default_rng(3)
    L, S = 11, 32
    prompt = rng.integers(1, cfg.vocab_size, L).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompt[None])}
    if cfg.family in ("audio", "vlm"):
        dim = cfg.encoder_len if cfg.family == "audio" else cfg.prefix_len
        batch["embeddings"] = jnp.asarray(
            rng.normal(size=(1, dim, cfg.d_model)), jnp.float32)
    extra = cfg.prefix_len if cfg.family == "vlm" else 0
    want, cache1 = api.prefill(params, cfg, batch, cache_size=S - extra)

    cache = api.init_cache(cfg, 1, S)
    pos = 0
    for j, bucket in enumerate((4, 4, 4)):       # 4+4+3: ragged final chunk
        cl = min(bucket, L - pos)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :cl] = prompt[pos:pos + cl]
        b = {"tokens": jnp.asarray(toks)}
        if j == 0 and "embeddings" in batch:
            b["embeddings"] = batch["embeddings"]
        got, cache = api.prefill_chunk(params, cfg, b, cache, chunk_len=cl)
        pos += cl
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert int(cache["len"]) == L + extra
    t1 = jnp.argmax(want, -1).astype(jnp.int32)
    t2 = jnp.argmax(got, -1).astype(jnp.int32)
    for _ in range(3):                           # caches decode identically
        l1, cache1 = api.decode_step(params, cfg, t1, cache1)
        l2, cache = api.decode_step(params, cfg, t2, cache)
        t1 = jnp.argmax(l1, -1).astype(jnp.int32)
        t2 = jnp.argmax(l2, -1).astype(jnp.int32)
        assert int(t1[0]) == int(t2[0]), family


# ---------------------------------------------------------------------------
# chunk-attention kernels: ref vs exact, Pallas (interpret) vs ref
# ---------------------------------------------------------------------------

def test_chunk_attention_ref_matches_exact_chain(rng):
    from repro.kernels import ref
    B, S, Hq, Hkv, D, L = 2, 32, 4, 2, 16, 20
    q = rng.normal(size=(B, L, Hq, D)).astype(np.float32)
    k = rng.normal(size=(B, L, Hkv, D)).astype(np.float32)
    v = rng.normal(size=(B, L, Hkv, D)).astype(np.float32)
    want = ref.mha_exact(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=True)
    kc = np.zeros((B, S, Hkv, D), np.float32)
    vc = np.zeros_like(kc)
    outs = []
    for lo, hi in ((0, 8), (8, 16), (16, 20)):
        T, cl = 8, hi - lo
        qch = np.zeros((B, T, Hq, D), np.float32)
        qch[:, :cl] = q[:, lo:hi]
        kc[:, lo:hi] = k[:, lo:hi]
        vc[:, lo:hi] = v[:, lo:hi]
        out = ref.chunk_attention_ref(jnp.asarray(qch), jnp.asarray(kc),
                                      jnp.asarray(vc), lo, cl)
        outs.append(np.asarray(out)[:, :cl])
    np.testing.assert_allclose(np.concatenate(outs, axis=1),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_chunk_prefill_attention_pallas_matches_ref(rng):
    from repro.kernels import ref
    from repro.kernels.decode_attention import chunk_prefill_attention_pallas
    B, S, T, Hq, Hkv, D = 2, 40, 8, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, Hq, D)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    start = jnp.asarray(np.array([5, 17], np.int32))
    cl = jnp.asarray(np.array([8, 3], np.int32))
    want = ref.chunk_attention_ref(q, kc, vc, start, cl)
    got = chunk_prefill_attention_pallas(q, kc, vc, start, cl,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ops_chunk_attention_dispatch(rng):
    from repro.kernels import ops, paged_pool
    B, S, T, Hq, Hkv, D = 1, 16, 4, 2, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, Hq, D)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    out = ops.chunk_attention(q, kc, vc, 2, 4, impl="ref")
    assert out.shape == (B, T, Hq, D)
    assert np.isfinite(np.asarray(out)).all()
    kp = paged_pool.from_natural(jnp.asarray(
        rng.normal(size=(1, 5, 8, Hkv, D)).astype(np.float32)))
    vp = paged_pool.from_natural(jnp.asarray(
        rng.normal(size=(1, 5, 8, Hkv, D)).astype(np.float32)))
    bt = jnp.asarray(np.array([[0, 1]], np.int32))
    out = ops.paged_chunk_attention(q, kp, vp, bt, jnp.asarray([2]),
                                    jnp.asarray([4]), kv_heads=Hkv,
                                    impl="ref")
    assert out.shape == (B, T, Hq, D)


# ---------------------------------------------------------------------------
# arena: multi-token append (write_prefill's offset/partial mode)
# ---------------------------------------------------------------------------

def test_arena_append_rows_multi_token_matches_write_prefill(dense_cfg):
    """Writing a prompt chunk-by-chunk through the multi-token
    ``append_rows`` reconstructs the same pages as one-shot
    ``write_prefill`` — including unaligned chunk starts."""
    from repro.models import transformer as T
    from repro.serving.arena import KVArena

    params = T.init(jax.random.PRNGKey(0), dense_cfg)
    prompt = jnp.asarray(np.arange(1, 14, dtype=np.int32)[None])   # L=13
    a1 = KVArena(dense_cfg, T.init_cache, capacity=2, max_seq_len=32,
                 block_size=8)
    _, cache = T.prefill(params, dense_cfg, {"tokens": prompt},
                         cache_size=a1.slot_tokens)
    s1 = a1.alloc(20)
    a1.write_prefill(s1, cache, prompt_len=13)

    a2 = KVArena(dense_cfg, T.init_cache, capacity=2, max_seq_len=32,
                 block_size=8)
    s2 = a2.alloc(20)
    bt = jnp.asarray(a2.block_tables()[s2][None])
    lens = jnp.zeros((1,), jnp.int32)
    for lo, hi in ((0, 5), (5, 13)):           # 5 is NOT block-aligned
        n = hi - lo
        dense = [jnp.zeros((leaf.shape[0], 1, a2.slot_tokens,
                            *leaf.shape[3:]), leaf.dtype)
                 for leaf in (cache["k"], cache["v"])]
        dense = [d.at[:, :, lo:hi].set(src[:, :, lo:hi]) for d, src in
                 zip(dense, (cache["k"], cache["v"]))]
        a2.pages = a2.append_rows(
            a2.pages, dense, lens + lo, jnp.ones((1,), bool), bt,
            n_tokens=n, valid_tokens=jnp.asarray([n]))
    v1 = a1.dense_view(a1.pages, jnp.asarray(a1.block_tables()[s1][None]))
    v2 = a2.dense_view(a2.pages, bt)
    for x, y in zip(v1, v2):
        np.testing.assert_allclose(np.asarray(x[:, :, :13]),
                                   np.asarray(y[:, :, :13]), rtol=1e-6)


# ---------------------------------------------------------------------------
# truthful timings under chunking (the decode_start_wall fix)
# ---------------------------------------------------------------------------

def test_decode_timing_excludes_chunked_prefill(dense_cfg):
    """A request that finishes on its first token (max_new_tokens=1) spends
    its whole life in prefill: ``decode_s`` must be exactly 0 even though
    several chunked steps elapsed between admission and the first token
    (the old code stamped decode_start_wall at admit time)."""
    from repro.models import transformer as T
    params = T.init(jax.random.PRNGKey(0), dense_cfg)
    rt = ServiceRuntime(dense_cfg, params,
                        ParallelPlan(service="t", category=LAT, bs=2),
                        max_seq_len=64, block_size=8)
    rt.submit(GenerationRequest(rid=0,
                                tokens=np.arange(1, 50, dtype=np.int32),
                                max_new_tokens=1))
    res = rt.drain()
    assert len(res) == 1
    assert res[0].prefill_s > 0.0
    assert res[0].decode_s == 0.0
    assert rt.prefill_chunk_calls >= 3          # 49 tokens, 16-token budget


def test_step_stats_report_chunk_tokens(dense_cfg):
    """StepStats.prefill_chunk_tokens accounts every prompt token exactly
    once, and in-progress prefills hold their slot (in_flight) without
    decoding."""
    from repro.models import transformer as T
    params = T.init(jax.random.PRNGKey(0), dense_cfg)
    rt = ServiceRuntime(dense_cfg, params,
                        ParallelPlan(service="t", category=LAT, bs=2),
                        max_seq_len=64, block_size=8)
    prompt = np.arange(1, 40, dtype=np.int32)          # 39 tokens > budget
    rt.submit(GenerationRequest(rid=0, tokens=prompt, max_new_tokens=2))
    stats = rt.step()
    assert stats.admitted == 1 and stats.in_flight == 1
    assert 0 < stats.prefill_chunk_tokens < len(prompt)
    assert stats.decode_steps == 0              # nothing decodable yet
    total = stats.prefill_chunk_tokens
    while rt.pending() or rt.in_flight():
        stats = rt.step()
        total += stats.prefill_chunk_tokens
    assert total == len(prompt)
