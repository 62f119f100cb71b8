"""Shared neural-net layers for the model zoo.

Pure-functional: params are nested dicts of jnp arrays; every forward takes
(params, cfg, ...).  Attention flows through ``repro.kernels.ops`` so the
same model code runs the jnp reference (XLA / dry-run) or the Pallas TPU
kernels.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels import paged_pool
from .config import ModelConfig


# ---------------------------------------------------------------------------
# initializers / primitives
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x, w, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, w, b, eps: float):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def init_norm(key, cfg: ModelConfig, dim: Optional[int] = None):
    d = dim or cfg.d_model
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,), cfg.weight_dtype),
                "b": jnp.zeros((d,), cfg.weight_dtype)}
    return {"w": jnp.ones((d,), cfg.weight_dtype)}


def apply_norm(p, cfg: ModelConfig, x):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["w"], p["b"], cfg.rms_eps)
    return rms_norm(x, p["w"], cfg.rms_eps)


def linear(x, w, b=None):
    y = jnp.einsum("...i,io->...o", x, w)
    if b is not None:
        y = y + b
    return y


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float):
    """x: (..., L, H, D) rotated by ``positions`` (broadcastable to (..., L))."""
    D = x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., L, half)
    sin = jnp.sin(ang)[..., None, :]
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, d: int):
    """Whisper-style sinusoidal positional embedding table (length, d)."""
    return sinusoid_at(jnp.arange(length), d)


def sinusoid_at(pos, d: int):
    """Sinusoidal embedding at arbitrary (possibly per-slot) positions:
    pos (B,) -> (B, d).  The decode path uses this with each slot's own
    ``len`` so requests at different depths share one fused step."""
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / (half - 1))
    ang = jnp.asarray(pos, jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, *, d_in: Optional[int] = None,
                   cross: bool = False):
    d = d_in or cfg.d_model
    hd, nq, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    dt = cfg.weight_dtype
    ks = jax.random.split(key, 4)
    if cfg.fused_projections and not cross:
        p = {
            "wqkv": dense_init(ks[0], (d, (nq + 2 * nkv) * hd), dt),
            "wo": dense_init(ks[3], (nq * hd, cfg.d_model), dt),
        }
        if cfg.qkv_bias:
            p["bqkv"] = jnp.zeros(((nq + 2 * nkv) * hd,), dt)
        return p
    p = {
        "wq": dense_init(ks[0], (d, nq * hd), dt),
        "wk": dense_init(ks[1], (cfg.d_model if cross else d, nkv * hd), dt),
        "wv": dense_init(ks[2], (cfg.d_model if cross else d, nkv * hd), dt),
        "wo": dense_init(ks[3], (nq * hd, cfg.d_model), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    return p


def _split_qkv_flat(cfg: ModelConfig, qkv):
    hd, nq, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    q = qkv[..., :nq * hd]
    k = qkv[..., nq * hd:(nq + nkv) * hd]
    v = qkv[..., (nq + nkv) * hd:]
    return q, k, v


def _project_qkv(p, cfg: ModelConfig, x, kv_x=None):
    B = x.shape[0]
    Lq = x.shape[1]
    kv_x = x if kv_x is None else kv_x
    Lk = kv_x.shape[1]
    if "wqkv" in p:
        q, k, v = _split_qkv_flat(cfg, linear(x, p["wqkv"], p.get("bqkv")))
    else:
        q = linear(x, p["wq"], p.get("bq"))
        k = linear(kv_x, p["wk"], p.get("bk"))
        v = linear(kv_x, p["wv"], p.get("bv"))
    q = q.reshape(B, Lq, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, Lk, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, Lk, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def attention(p, cfg: ModelConfig, x, *, positions=None, causal=True,
              window=None, prefix_len=0, kv_x=None, use_rope=True,
              impl=None):
    """Full (prefill/train) attention.  Returns (out, (k, v)) so callers can
    seed a KV cache; ``kv_x`` switches to cross-attention (no mask/rope on kv
    unless self)."""
    B, Lq, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, kv_x)
    if use_rope:
        if positions is None:
            positions = jnp.arange(Lq)[None]
        q = rope(q, positions, cfg.rope_theta)
        if kv_x is None:
            k = rope(k, positions, cfg.rope_theta)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              prefix_len=prefix_len, impl=impl)
    out = out.reshape(B, Lq, cfg.num_heads * cfg.head_dim)
    return linear(out, p["wo"]), (k, v)


def attention_decode(p, cfg: ModelConfig, x_t, k_cache, v_cache, cache_len, *,
                     position=None, window=None, use_rope=True, impl=None):
    """One-token decode: x_t (B, d) vs caches (B, S, Hkv, hd).

    ``cache_len`` counts valid entries *including* the token being written
    at ring slot ``(cache_len-1) % S``.  Returns (out (B, d), k_t, v_t) —
    cache insertion is the caller's (serving.kvcache) job, so this function
    stays functional.
    """
    B = x_t.shape[0]
    if "wqkv" in p:
        q, k_t, v_t = _split_qkv_flat(
            cfg, linear(x_t, p["wqkv"], p.get("bqkv")))
    else:
        q = linear(x_t, p["wq"], p.get("bq"))
        k_t = linear(x_t, p["wk"], p.get("bk"))
        v_t = linear(x_t, p["wv"], p.get("bv"))
    q = q.reshape(B, cfg.num_heads, cfg.head_dim)
    k_t = k_t.reshape(B, cfg.num_kv_heads, cfg.head_dim)
    v_t = v_t.reshape(B, cfg.num_kv_heads, cfg.head_dim)
    if use_rope:
        pos = (cache_len - 1) if position is None else position
        pos = jnp.asarray(pos)
        if pos.ndim == 0:
            pos = jnp.full((B,), pos)
        q = rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        k_t = rope(k_t[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    S = k_cache.shape[1]
    slot = (jnp.asarray(cache_len) - 1) % S
    if slot.ndim == 0:
        slot = jnp.full((B,), slot)

    def _insert(cache, s, t):
        return jax.lax.dynamic_update_slice(cache, t[None], (s, 0, 0))

    k_cache = jax.vmap(_insert)(k_cache, slot, k_t.astype(k_cache.dtype))
    v_cache = jax.vmap(_insert)(v_cache, slot, v_t.astype(v_cache.dtype))
    eff_len = jnp.minimum(jnp.asarray(cache_len), S)
    out = ops.decode_attention(q, k_cache, v_cache, eff_len,
                               window=window, impl=impl)
    out = out.reshape(B, cfg.num_heads * cfg.head_dim)
    return linear(out, p["wo"]), k_cache, v_cache


def attention_chunk(p, cfg: ModelConfig, x, k_cache, v_cache, cache_len,
                    chunk_len, *, window=None, prefix_len=0, use_rope=True,
                    impl=None):
    """Chunked-prefill attention: append a block of T tokens to a cache
    that already holds ``cache_len`` tokens (the piggybacked-prefill path).

    x: (B, T, d) right-padded to the static bucket size T; only the first
    ``chunk_len`` rows are real.  The chunk's K/V are written at positions
    ``cache_len + i`` for i < chunk_len (padding rows target index S, which
    the scatter drops), then the chunk queries attend causally over the
    whole cache via ``ops.chunk_attention`` — so one trace serves every
    (start, chunk_len) at a given bucket size.  Returns (out (B, T, d),
    k_cache, v_cache); rows past ``chunk_len`` are garbage the caller
    discards.
    """
    B, T, _ = x.shape
    S = k_cache.shape[1]
    if window is not None and S > window:
        raise NotImplementedError(
            "chunked prefill does not support ring (sliding-window) cache "
            "layouts; the engine gates those to one-shot prefill")
    q, k_t, v_t = _project_qkv(p, cfg, x)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    if chunk_len.ndim == 0:
        chunk_len = jnp.full((B,), chunk_len)
    positions = cache_len[:, None] + jnp.arange(T)[None]      # (B, T)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k_t = rope(k_t, positions, cfg.rope_theta)
    # scatter the chunk's K/V rows; padded rows index S and are dropped
    idx = jnp.where(jnp.arange(T)[None] < chunk_len[:, None],
                    positions, S)

    def _insert(cache, i, t):
        return cache.at[i].set(t)

    k_cache = jax.vmap(_insert)(k_cache, idx, k_t.astype(k_cache.dtype))
    v_cache = jax.vmap(_insert)(v_cache, idx, v_t.astype(v_cache.dtype))
    out = ops.chunk_attention(q, k_cache, v_cache, cache_len, chunk_len,
                              prefix_len=prefix_len, impl=impl)
    out = out.reshape(B, T, cfg.num_heads * cfg.head_dim)
    return linear(out, p["wo"]), k_cache, v_cache


def paged_insert_rows(pages, rows, block_tables, positions, valid, *,
                      block_size: int, layer=0):
    """Scatter per-slot K/V rows straight into a stacked page pool.

    pages: the arena's pool of every layer in the ``paged_pool`` layout
    (its LAST page is the reserved trash block), written at ``layer``;
    rows: (B, T, Hkv, D) new cache rows; positions: (B, T) absolute token
    positions; valid: (B, T) bool — invalid rows (dead slots, chunk
    padding) land in the trash page, so the scatter stays branch-free and
    shape-stable.  This is the paged-native write path: one row per
    produced token, written in place into the stored buffer (no per-layer
    slice of the pool, no relayout), never the dense re-scatter of the
    whole view.  A ``QuantPages`` pool quantizes the fresh float rows on
    insert (int8 rows to the values, their f32 scales to the scale rows).
    """
    trash = paged_pool.pages_of(pages) - 1
    nblk = block_tables.shape[1]
    pos = jnp.clip(positions, 0, nblk * block_size - 1)
    blk = jnp.take_along_axis(block_tables, pos // block_size, axis=1)
    blk = jnp.where(valid, blk, trash).reshape(-1)
    off = jnp.where(valid, pos % block_size, 0).reshape(-1)
    B, T = rows.shape[:2]
    return paged_pool.write_rows(pages, rows.reshape(B * T, *rows.shape[2:]),
                                 blk, off, layer=layer)


def _no_paged_ring(window, total_tokens: int) -> None:
    if window is not None and window < total_tokens:
        raise NotImplementedError(
            "paged-native attention does not support ring (sliding-window) "
            "cache layouts; the engine gates those to the dense-view path")


def attention_decode_paged(p, cfg: ModelConfig, x_t, k_pages, v_pages,
                           block_tables, lens, live, *, block_size: int,
                           layer=0, window=None, use_rope=True, impl=None):
    """One-token decode against the serving arena's paged KV layout.

    x_t: (B, d); pages: the stacked pools of every layer (``paged_pool``
    layout), read and written at ``layer`` through ``block_tables`` (B,
    nblk); ``lens`` (B,) counts tokens already cached (the new token is
    written at position ``lens``).  Only the new K/V row is scattered back
    — attention reads K/V in place via ``ops.paged_decode_attention``, so
    the hot loop never materializes a dense view.  Numerically identical
    to ``attention_decode`` on the gathered view (same projections, rope
    positions and masking)."""
    B = x_t.shape[0]
    _no_paged_ring(window, block_tables.shape[1] * block_size)
    if "wqkv" in p:
        q, k_t, v_t = _split_qkv_flat(
            cfg, linear(x_t, p["wqkv"], p.get("bqkv")))
    else:
        q = linear(x_t, p["wq"], p.get("bq"))
        k_t = linear(x_t, p["wk"], p.get("bk"))
        v_t = linear(x_t, p["wv"], p.get("bv"))
    q = q.reshape(B, cfg.num_heads, cfg.head_dim)
    k_t = k_t.reshape(B, cfg.num_kv_heads, cfg.head_dim)
    v_t = v_t.reshape(B, cfg.num_kv_heads, cfg.head_dim)
    lens = jnp.asarray(lens, jnp.int32)
    if use_rope:
        q = rope(q[:, None], lens[:, None], cfg.rope_theta)[:, 0]
        k_t = rope(k_t[:, None], lens[:, None], cfg.rope_theta)[:, 0]
    ok = jnp.asarray(live, bool)[:, None]
    k_pages = paged_insert_rows(k_pages, k_t[:, None], block_tables,
                                lens[:, None], ok, block_size=block_size,
                                layer=layer)
    v_pages = paged_insert_rows(v_pages, v_t[:, None], block_tables,
                                lens[:, None], ok, block_size=block_size,
                                layer=layer)
    out = ops.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                     lens + 1, layer=layer,
                                     kv_heads=cfg.num_kv_heads, impl=impl)
    out = out.reshape(B, cfg.num_heads * cfg.head_dim)
    return linear(out, p["wo"]), k_pages, v_pages


def attention_chunk_paged(p, cfg: ModelConfig, x, k_pages, v_pages,
                          block_tables, cache_len, chunk_len, *,
                          block_size: int, layer=0, window=None,
                          prefix_len=0, use_rope=True, impl=None,
                          verify=False):
    """Chunked-prefill attention against the paged KV layout: append a
    right-padded T-token chunk (only the first ``chunk_len`` rows real)
    at positions ``cache_len + i`` directly into the stacked pools at
    ``layer``, then attend through the block table via
    ``ops.paged_chunk_attention``.  The
    multi-token sibling of ``attention_decode_paged`` (and the paged
    mirror of ``attention_chunk``).

    ``verify=True`` is the speculative-decoding verify contract: the SAME
    kernel path, but ``chunk_len`` is always a per-slot (B,) vector where
    0 marks non-speculating rows (their K/V writes route to the trash
    block and their attention rows are garbage the verifier masks) — it
    routes through ``ops.paged_verify_attention`` so the contract is
    asserted once, next to the kernels."""
    B, T, _ = x.shape
    _no_paged_ring(window, block_tables.shape[1] * block_size)
    q, k_t, v_t = _project_qkv(p, cfg, x)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    if chunk_len.ndim == 0:
        chunk_len = jnp.full((B,), chunk_len)
    positions = cache_len[:, None] + jnp.arange(T)[None]      # (B, T)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k_t = rope(k_t, positions, cfg.rope_theta)
    valid = jnp.arange(T)[None] < chunk_len[:, None]
    k_pages = paged_insert_rows(k_pages, k_t, block_tables, positions,
                                valid, block_size=block_size, layer=layer)
    v_pages = paged_insert_rows(v_pages, v_t, block_tables, positions,
                                valid, block_size=block_size, layer=layer)
    attend = ops.paged_verify_attention if verify else \
        ops.paged_chunk_attention
    out = attend(q, k_pages, v_pages, block_tables, cache_len, chunk_len,
                 layer=layer, kv_heads=cfg.num_kv_heads,
                 prefix_len=prefix_len, impl=impl)
    out = out.reshape(B, T, cfg.num_heads * cfg.head_dim)
    return linear(out, p["wo"]), k_pages, v_pages


def cross_attention_decode(p, cfg: ModelConfig, x_t, memory, impl=None):
    """Decode-time cross attention against a fixed encoder memory."""
    B = x_t.shape[0]
    out, _ = attention(p, cfg, x_t[:, None], kv_x=memory, causal=False,
                       use_rope=False, impl=impl)
    return out[:, 0]


def take_chunk_last(x, chunk_len):
    """x: (B, T, ...) right-padded chunk activations -> the row at
    ``chunk_len - 1`` per batch (the last REAL token's hidden state, whose
    logits seed sampling when the chunk completes a prompt)."""
    B, T = x.shape[:2]
    cl = jnp.asarray(chunk_len, jnp.int32)
    if cl.ndim == 0:
        cl = jnp.full((B,), cl)
    idx = jnp.clip(cl - 1, 0, T - 1).reshape(
        (B, 1) + (1,) * (x.ndim - 2))
    return jnp.take_along_axis(x, idx, axis=1)[:, 0]


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, *, d_in: Optional[int] = None,
             d_ff: Optional[int] = None):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    dt = cfg.weight_dtype
    ks = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        if cfg.fused_projections:
            return {"w_gateup": dense_init(ks[0], (d, 2 * f), dt),
                    "w_down": dense_init(ks[2], (f, cfg.d_model), dt)}
        return {"w_gate": dense_init(ks[0], (d, f), dt),
                "w_up": dense_init(ks[1], (d, f), dt),
                "w_down": dense_init(ks[2], (f, cfg.d_model), dt)}
    return {"w_up": dense_init(ks[0], (d, f), dt),
            "w_down": dense_init(ks[1], (f, cfg.d_model), dt)}


def mlp(p, cfg: ModelConfig, x):
    if "w_gateup" in p:
        gu = linear(x, p["w_gateup"])
        f = gu.shape[-1] // 2
        act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        h = act(gu[..., :f]) * gu[..., f:]
    elif cfg.activation == "swiglu":
        h = jax.nn.silu(linear(x, p["w_gate"])) * linear(x, p["w_up"])
    elif cfg.activation == "geglu":
        h = jax.nn.gelu(linear(x, p["w_gate"])) * linear(x, p["w_up"])
    else:  # gelu_mlp
        h = jax.nn.gelu(linear(x, p["w_up"]))
    return linear(h, p["w_down"])


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    p = {"embedding": dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                                 cfg.weight_dtype, scale=1.0)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                  cfg.weight_dtype)
    return p


def embed(p, cfg: ModelConfig, tokens):
    return jnp.take(p["embedding"], tokens, axis=0)


def unembed(p, cfg: ModelConfig, h):
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", h, p["embedding"])
    return jnp.einsum("...d,dv->...v", h, p["unembed"])
