"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracles,
swept over shapes/dtypes, plus flash-backward gradient checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gemm import grouped_matmul_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

TOL = dict(rtol=2e-3, atol=2e-3)


def _qkv(key, B, Lq, Lk, Hq, Hkv, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Lq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Lk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Lk, Hkv, D), dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Lq,Lk,Hq,Hkv,D", [
    (1, 64, 64, 4, 4, 32),      # MHA square
    (2, 40, 72, 8, 2, 16),      # GQA ragged
    (1, 16, 128, 4, 1, 64),     # MQA, Lk > Lq
])
def test_flash_vs_exact(dtype, B, Lq, Lk, Hq, Hkv, D):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, Lq, Lk, Hq, Hkv, D, dtype)
    want = ref.mha_exact(q, k, v, causal=True, q_offset=Lk - Lq)
    got = flash_attention_pallas(q, k, v, causal=True, q_offset=Lk - Lq,
                                 q_block=16, k_block=16, interpret=True)
    tol = TOL if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("kwargs", [
    dict(causal=True, window=16),
    dict(causal=False),
    dict(causal=True, prefix_len=8),
    dict(causal=True, kv_len=50),
    dict(causal=True, window=8, prefix_len=4),
])
def test_flash_masks(kwargs):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 48, 64, 4, 2, 32, jnp.float32)
    want = ref.mha_exact(q, k, v, q_offset=16, **kwargs)
    got = flash_attention_pallas(q, k, v, q_offset=16, q_block=16,
                                 k_block=16, interpret=True, **kwargs)
    np.testing.assert_allclose(got, want, **TOL)


def test_flash_ref_matches_exact_large_blocks():
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 100, 100, 4, 4, 16,
                   jnp.float32)
    want = ref.mha_exact(q, k, v)
    got = ref.flash_attention_ref(q, k, v, q_chunk=33, k_chunk=17)
    np.testing.assert_allclose(got, want, **TOL)


def test_flash_custom_vjp_grads():
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 24, 24, 4, 2, 16, jnp.float32)

    def f_exact(q, k, v):
        return (ref.mha_exact(q, k, v, causal=True, window=9) ** 2).sum()

    def f_flash(q, k, v):
        return (ops.flash_attention(q, k, v, causal=True, window=9,
                                    impl="ref") ** 2).sum()

    g_want = jax.grad(f_exact, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(lq=st.integers(4, 40), lk=st.integers(4, 40),
       window=st.one_of(st.none(), st.integers(1, 48)),
       group=st.sampled_from([1, 2, 4]))
def test_flash_property_mask_semantics(lq, lk, window, group):
    """Property: blocked flash == exact attention for arbitrary sizes,
    windows, and GQA group factors (the invariant each Pallas kernel must
    preserve)."""
    Hkv, D = 2, 8
    q, k, v = _qkv(jax.random.PRNGKey(lq * 131 + lk), 1, lq, lk,
                   Hkv * group, Hkv, D, jnp.float32)
    off = max(0, lk - lq)
    want = ref.mha_exact(q, k, v, causal=True, window=window, q_offset=off)
    got = ref.flash_attention_ref(q, k, v, causal=True, window=window,
                                  q_offset=off, q_chunk=8, k_chunk=8)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 16])
def test_decode_vs_ref(dtype, window):
    B, S, Hq, Hkv, D = 3, 96, 8, 2, 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, Hq, D), dtype)
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D), dtype)
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D), dtype)
    lens = jnp.array([96, 40, 7])
    want = ref.decode_attention_ref(q, kc, vc, lens, window=window)
    got = decode_attention_pallas(q, kc, vc, lens, window=window,
                                  k_block=16, interpret=True)
    tol = TOL if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_decode_matches_exact_single():
    """Decode vs a 1-query exact attention at each valid length."""
    B, S, Hq, Hkv, D = 1, 33, 4, 4, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, Hq, D))
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D))
    for L in (1, 17, 33):
        got = ref.decode_attention_ref(q, kc, vc, L)
        want = ref.mha_exact(q[:, None], kc[:, :L], vc[:, :L],
                             causal=False)[:, 0]
        np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

def _ssd_inputs(key, Bb, L, H, P, G, N):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (Bb, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bb, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bb, L, G, N))
    C = jax.random.normal(ks[4], (Bb, L, G, N))
    D = jnp.ones((H,))
    return x, dt, A, B, C, D


@pytest.mark.parametrize("chunk", [8, 32, 128])
@pytest.mark.parametrize("L", [17, 64])
def test_ssd_chunked_vs_exact(chunk, L):
    x, dt, A, B, C, D = _ssd_inputs(jax.random.PRNGKey(0), 2, L, 4, 8, 2, 4)
    y1, h1 = ref.ssd_exact(x, dt, A, B, C, D)
    y2, h2 = ref.ssd_chunked_ref(x, dt, A, B, C, D, chunk=chunk)
    np.testing.assert_allclose(y2, y1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h2, h1, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("L,chunk", [(64, 16), (50, 16)])
def test_ssd_pallas_vs_ref(L, chunk):
    x, dt, A, B, C, D = _ssd_inputs(jax.random.PRNGKey(1), 2, L, 4, 16, 2, 8)
    h0 = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 16, 8))
    y1, h1 = ref.ssd_chunked_ref(x, dt, A, B, C, D, chunk=chunk,
                                 initial_state=h0)
    y2, h2 = ssd_scan_pallas(x, dt, A, B, C, D, chunk=chunk,
                             initial_state=h0, interpret=True)
    np.testing.assert_allclose(y2, y1, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(h2, h1, rtol=1e-3, atol=1e-3)


def test_ssd_decode_step_consistency():
    """Chunked prefill then recurrent steps == full chunked run."""
    x, dt, A, B, C, D = _ssd_inputs(jax.random.PRNGKey(2), 1, 20, 2, 8, 1, 4)
    y_all, h_all = ref.ssd_chunked_ref(x, dt, A, B, C, D, chunk=8)
    y_pre, h = ref.ssd_chunked_ref(x[:, :15], dt[:, :15], A, B[:, :15],
                                   C[:, :15], D, chunk=8)
    for t in range(15, 20):
        y_t, h = ref.ssd_decode_step_ref(h, x[:, t], dt[:, t], A, B[:, t],
                                         C[:, t], D)
        np.testing.assert_allclose(y_t, y_all[:, t], rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(L=st.integers(2, 40), chunk=st.sampled_from([4, 8, 16]),
       G=st.sampled_from([1, 2]))
def test_ssd_property_chunk_invariance(L, chunk, G):
    """Property: the output is invariant to the chunk size (the kernel's
    tiling must not change the math)."""
    H = 2 * G
    x, dt, A, B, C, D = _ssd_inputs(jax.random.PRNGKey(L * 7 + chunk),
                                    1, L, H, 4, G, 4)
    y1, h1 = ref.ssd_chunked_ref(x, dt, A, B, C, D, chunk=chunk)
    y2, h2 = ref.ssd_exact(x, dt, A, B, C, D)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h1, h2, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,K,N", [(4, 50, 70, 33), (2, 128, 64, 128),
                                     (8, 10, 200, 16)])
def test_grouped_matmul(dtype, E, C, K, N):
    lhs = jax.random.normal(jax.random.PRNGKey(0), (E, C, K), dtype)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (E, K, N), dtype)
    want = ref.grouped_matmul_ref(lhs, rhs)
    got = grouped_matmul_pallas(lhs, rhs, block_c=16, block_n=16,
                                block_k=32, interpret=True)
    tol = dict(rtol=1e-4, atol=1e-3) if dtype == jnp.float32 \
        else dict(rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_ops_dispatch_env(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "bogus")
    with pytest.raises(ValueError):
        ops.default_impl()
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas_interpret")
    assert ops.default_impl() == "pallas_interpret"
    # unset, the backend decides; the compiled kernel is never asked of a
    # CPU, and never silently interpreted there
    monkeypatch.delenv("REPRO_KERNEL_IMPL")
    assert ops.default_impl() == ("pallas" if jax.default_backend() == "tpu"
                                  else "ref")
    if jax.default_backend() != "tpu":
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
        with pytest.raises(ValueError, match="TPU"):
            ops.default_impl()


# ---------------------------------------------------------------------------
# flash attention backward kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs", [
    dict(causal=True),
    dict(causal=True, window=9),
    dict(causal=False),
    dict(causal=True, prefix_len=7),
])
def test_flash_bwd_pallas_vs_ref(kwargs):
    from repro.kernels.flash_attention_bwd import flash_attention_bwd_pallas
    B, Lq, Lk, Hq, Hkv, D = 2, 40, 56, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, Lq, Hq, D))
    k = jax.random.normal(ks[1], (B, Lk, Hkv, D))
    v = jax.random.normal(ks[2], (B, Lk, Hkv, D))
    do = jax.random.normal(ks[3], (B, Lq, Hq, D))
    out, lse = ref.flash_attention_fwd_ref(q, k, v, **kwargs)
    want = ref.flash_attention_bwd_ref(q, k, v, out, lse, do, **kwargs)
    got = flash_attention_bwd_pallas(q, k, v, out, lse, do, q_block=16,
                                     k_block=16, interpret=True, **kwargs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_flash_pallas_lse_matches_ref():
    from repro.kernels.flash_attention import flash_attention_pallas
    q, k, v = _qkv(jax.random.PRNGKey(5), 2, 33, 48, 4, 2, 16, jnp.float32)
    o1, l1 = ref.flash_attention_fwd_ref(q, k, v, causal=True, window=11)
    o2, l2 = flash_attention_pallas(q, k, v, causal=True, window=11,
                                    q_block=16, k_block=16,
                                    return_lse=True, interpret=True)
    np.testing.assert_allclose(o2, o1, **TOL)
    np.testing.assert_allclose(l2, l1, rtol=1e-5, atol=1e-5)


def test_full_pallas_train_grads_vs_exact():
    """End-to-end: pallas fwd (with lse) + pallas bwd under jax.grad
    matches autodiff through the exact oracle."""
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 28, 28, 4, 2, 16, jnp.float32)

    def f(q, k, v):
        return (ops.flash_attention(q, k, v, causal=True, window=11,
                                    impl="pallas_interpret") ** 2).sum()

    def fe(q, k, v):
        return (ref.mha_exact(q, k, v, causal=True, window=11) ** 2).sum()

    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(fe, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
