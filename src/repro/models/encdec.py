"""Whisper-large-v3 TRANSFORMER BACKBONE (encoder-decoder).

Per the assignment carve-out, the modality frontend (mel-spectrogram +
conv feature extractor) is a STUB: ``input_specs`` feeds precomputed frame
embeddings (B, encoder_len, d_model).  This module implements the
language/decoder transformer that consumes them: a non-causal encoder
stack and a causal decoder with self- + cross-attention.

Divergence note (DESIGN.md §4): whisper's learned absolute positions are
replaced by parameter-free sinusoidal positions so the backbone lowers at
the assigned 32k/500k decode shapes (the real model caps at 448 positions).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from . import layers, transformer
from .config import ModelConfig
from .sharding import constrain_activation


def init_encoder_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "ln1": layers.init_norm(ks[0], cfg),
        "attn": layers.init_attention(ks[1], cfg),
        "ln2": layers.init_norm(ks[2], cfg),
        "mlp": layers.init_mlp(ks[3], cfg),
    }


def init_decoder_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    return {
        "ln1": layers.init_norm(ks[0], cfg),
        "self_attn": layers.init_attention(ks[1], cfg),
        "ln_x": layers.init_norm(ks[2], cfg),
        "cross_attn": layers.init_attention(ks[3], cfg, cross=True),
        "ln2": layers.init_norm(ks[4], cfg),
        "mlp": layers.init_mlp(ks[5], cfg),
    }


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    return {
        "embed": layers.init_embedding(ks[0], cfg),
        "enc_blocks": transformer.stack_layer_params(
            ks[1], cfg.encoder_layers, lambda k: init_encoder_block(k, cfg)),
        "ln_enc": layers.init_norm(ks[2], cfg),
        "dec_blocks": transformer.stack_layer_params(
            ks[3], cfg.num_layers, lambda k: init_decoder_block(k, cfg)),
        "ln_f": layers.init_norm(ks[4], cfg),
    }


def encode(params, cfg: ModelConfig, frame_embeddings, *, impl=None):
    """frame_embeddings: (B, T, d) stub frontend output -> encoder memory."""
    B, T, d = frame_embeddings.shape
    h = frame_embeddings.astype(cfg.compute_dtype)
    h = h + layers.sinusoidal_positions(T, d)[None].astype(h.dtype)

    def body(carry, lp):
        carry = constrain_activation(carry)
        a, _ = layers.attention(lp["attn"], cfg,
                                layers.apply_norm(lp["ln1"], cfg, carry),
                                causal=False, use_rope=False, impl=impl)
        x = carry + a
        x = x + layers.mlp(lp["mlp"], cfg,
                           layers.apply_norm(lp["ln2"], cfg, x))
        return x, None

    h, _ = jax.lax.scan(body, h, params["enc_blocks"])
    return layers.apply_norm(params["ln_enc"], cfg, h)


def _decoder_tokens(params, cfg: ModelConfig, tokens, offset: int = 0):
    B, L = tokens.shape
    h = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    pos = layers.sinusoidal_positions(offset + L, cfg.d_model)[offset:]
    return h + pos[None].astype(h.dtype)


def forward_hidden(params, cfg: ModelConfig, batch: Dict[str, Any], *,
                   train: bool = False, impl=None):
    """Teacher-forced decoder over ``tokens`` given stub frame embeddings."""
    memory = encode(params, cfg, batch["embeddings"], impl=impl)
    h = _decoder_tokens(params, cfg, batch["tokens"])
    window = cfg.sliding_window

    def body(carry, lp):
        carry = constrain_activation(carry)
        a, _ = layers.attention(lp["self_attn"], cfg,
                                layers.apply_norm(lp["ln1"], cfg, carry),
                                causal=True, window=window, use_rope=False,
                                impl=impl)
        x = carry + a
        c, _ = layers.attention(lp["cross_attn"], cfg,
                                layers.apply_norm(lp["ln_x"], cfg, x),
                                kv_x=memory, causal=False, use_rope=False,
                                impl=impl)
        x = x + c
        x = x + layers.mlp(lp["mlp"], cfg,
                           layers.apply_norm(lp["ln2"], cfg, x))
        return x, None

    scan_body = jax.checkpoint(body) if train else body
    h, _ = jax.lax.scan(scan_body, h, params["dec_blocks"])
    h = layers.apply_norm(params["ln_f"], cfg, h)
    return h, jnp.zeros((), jnp.float32)


def logits_fn(params, cfg: ModelConfig, hidden):
    return layers.unembed(params["embed"], cfg, hidden)


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int, dtype=None):
    dtype = dtype or cfg.compute_dtype
    window = cfg.sliding_window
    S = min(max_len, window) if window is not None else max_len
    kv = (cfg.num_layers, batch_size, S, cfg.num_kv_heads, cfg.head_dim)
    xkv = (cfg.num_layers, batch_size, cfg.encoder_len, cfg.num_kv_heads,
           cfg.head_dim)
    return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
            "cross_k": jnp.zeros(xkv, dtype), "cross_v": jnp.zeros(xkv, dtype),
            "len": jnp.zeros((), jnp.int32)}


def prefill(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            cache_size: Optional[int] = None, impl=None):
    memory = encode(params, cfg, batch["embeddings"], impl=impl)
    tokens = batch["tokens"]
    B, L = tokens.shape
    window = cfg.sliding_window
    cache_size = cache_size or L
    if window is not None:
        cache_size = min(cache_size, window)
    else:
        cache_size = max(cache_size, L)  # full attention never trims
    h = _decoder_tokens(params, cfg, tokens)

    def body(carry, lp):
        carry = constrain_activation(carry)
        xn = layers.apply_norm(lp["ln1"], cfg, carry)
        a, (k, v) = layers.attention(lp["self_attn"], cfg, xn, causal=True,
                                     window=window, use_rope=False, impl=impl)
        x = carry + a
        xn = layers.apply_norm(lp["ln_x"], cfg, x)
        c, (ck, cv) = layers.attention(lp["cross_attn"], cfg, xn, kv_x=memory,
                                       causal=False, use_rope=False, impl=impl)
        x = x + c
        x = x + layers.mlp(lp["mlp"], cfg,
                           layers.apply_norm(lp["ln2"], cfg, x))
        if cache_size > L:
            pad = ((0, 0), (0, cache_size - L), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        elif cache_size < L:
            k, v = k[:, L - cache_size:], v[:, L - cache_size:]
            shift = L % cache_size
            k, v = jnp.roll(k, shift, axis=1), jnp.roll(v, shift, axis=1)
        return x, (k, v, ck, cv)

    h, (k, v, ck, cv) = jax.lax.scan(body, h, params["dec_blocks"])
    h = layers.apply_norm(params["ln_f"], cfg, h[:, -1:])
    logits = logits_fn(params, cfg, h[:, 0])
    cache = {"k": k, "v": v, "cross_k": ck, "cross_v": cv,
             "len": jnp.asarray(L, jnp.int32)}
    return logits, cache


def prefill_chunk(params, cfg: ModelConfig, batch, cache, *, chunk_len,
                  impl=None):
    """Chunked decoder prefill.  The FIRST chunk carries
    ``batch["embeddings"]``: it runs the encoder once and projects the
    cross-attention K/V into the cache's ``cross_k``/``cross_v`` rows;
    later chunks reuse them (the encoder never re-runs).  Decoder self-
    attention appends the chunk like ``transformer.prefill_chunk`` (no
    rope — sinusoidal positions ride on the embeddings at the chunk's
    absolute offset)."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    window = cfg.sliding_window
    start = cache["len"]
    startv = jnp.asarray(start, jnp.int32).reshape(-1) * jnp.ones(
        (B,), jnp.int32)
    h = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    pos = (startv[:, None] + jnp.arange(T)[None]).reshape(-1)
    h = h + layers.sinusoid_at(pos, cfg.d_model).reshape(
        B, T, cfg.d_model).astype(h.dtype)
    first = "embeddings" in batch
    memory = (encode(params, cfg, batch["embeddings"], impl=impl)
              if first else None)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i, ck, cv = xs
        x = constrain_activation(x)
        if first:                   # project this layer's cross K/V once
            Lk = memory.shape[1]
            ck = layers.linear(memory, lp["cross_attn"]["wk"],
                               lp["cross_attn"].get("bk")).reshape(
                B, Lk, cfg.num_kv_heads, cfg.head_dim).astype(ck.dtype)
            cv = layers.linear(memory, lp["cross_attn"]["wv"],
                               lp["cross_attn"].get("bv")).reshape(
                B, Lk, cfg.num_kv_heads, cfg.head_dim).astype(cv.dtype)
        kc = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        xn = layers.apply_norm(lp["ln1"], cfg, x)
        a, kc, vc = layers.attention_chunk(lp["self_attn"], cfg, xn, kc, vc,
                                           startv, chunk_len, window=window,
                                           use_rope=False, impl=impl)
        x = x + a
        xn = layers.apply_norm(lp["ln_x"], cfg, x)
        q = layers.linear(xn, lp["cross_attn"]["wq"],
                          lp["cross_attn"].get("bq")).reshape(
            B, T, cfg.num_heads, cfg.head_dim)
        c = ops.flash_attention(q, ck, cv, causal=False, impl=impl)
        c = layers.linear(c.reshape(B, T, -1), lp["cross_attn"]["wo"])
        x = x + c
        x = x + layers.mlp(lp["mlp"], cfg,
                           layers.apply_norm(lp["ln2"], cfg, x))
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, i, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, i, 0)
        return (x, k_all, v_all), (ck, cv)

    (h, k, v), (ck_all, cv_all) = jax.lax.scan(
        body, (h, cache["k"], cache["v"]),
        (params["dec_blocks"], jnp.arange(cfg.num_layers),
         cache["cross_k"], cache["cross_v"]))
    h = layers.take_chunk_last(h, chunk_len)
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "cross_k": ck_all, "cross_v": cv_all,
                    "len": cache["len"] + chunk_len}


def prefill_chunk_paged(params, cfg: ModelConfig, batch, cache,
                        block_tables, *, chunk_len, block_size, impl=None):
    """Paged-native chunked decoder prefill (see ``prefill_chunk``): the
    decoder self-attention K/V rows scatter straight into the arena page
    pools; the cross-attention K/V stay per-slot STATE (fixed
    ``encoder_len`` — the arena never pages them) and are projected once
    by the first chunk exactly as in the dense path."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    window = cfg.sliding_window
    start = jnp.asarray(cache["len"], jnp.int32).reshape(-1)
    startv = start * jnp.ones((B,), jnp.int32)
    h = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    pos = (startv[:, None] + jnp.arange(T)[None]).reshape(-1)
    h = h + layers.sinusoid_at(pos, cfg.d_model).reshape(
        B, T, cfg.d_model).astype(h.dtype)
    first = "embeddings" in batch
    memory = (encode(params, cfg, batch["embeddings"], impl=impl)
              if first else None)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i, ck, cv = xs
        x = constrain_activation(x)
        if first:                   # project this layer's cross K/V once
            Lk = memory.shape[1]
            ck = layers.linear(memory, lp["cross_attn"]["wk"],
                               lp["cross_attn"].get("bk")).reshape(
                B, Lk, cfg.num_kv_heads, cfg.head_dim).astype(ck.dtype)
            cv = layers.linear(memory, lp["cross_attn"]["wv"],
                               lp["cross_attn"].get("bv")).reshape(
                B, Lk, cfg.num_kv_heads, cfg.head_dim).astype(cv.dtype)
        xn = layers.apply_norm(lp["ln1"], cfg, x)
        a, k_all, v_all = layers.attention_chunk_paged(
            lp["self_attn"], cfg, xn, k_all, v_all, block_tables, startv,
            chunk_len, block_size=block_size, layer=i, window=window,
            use_rope=False, impl=impl)
        x = x + a
        xn = layers.apply_norm(lp["ln_x"], cfg, x)
        q = layers.linear(xn, lp["cross_attn"]["wq"],
                          lp["cross_attn"].get("bq")).reshape(
            B, T, cfg.num_heads, cfg.head_dim)
        c = ops.flash_attention(q, ck, cv, causal=False, impl=impl)
        c = layers.linear(c.reshape(B, T, -1), lp["cross_attn"]["wo"])
        x = x + c
        x = x + layers.mlp(lp["mlp"], cfg,
                           layers.apply_norm(lp["ln2"], cfg, x))
        return (x, k_all, v_all), (ck, cv)

    (h, k, v), (ck_all, cv_all) = jax.lax.scan(
        body, (h, cache["k"], cache["v"]),
        (params["dec_blocks"], jnp.arange(cfg.num_layers),
         cache["cross_k"], cache["cross_v"]))
    h = layers.take_chunk_last(h, chunk_len)
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "cross_k": ck_all, "cross_v": cv_all,
                    "len": start + chunk_len}


def decode_step(params, cfg: ModelConfig, token, cache, impl=None):
    B = token.shape[0]
    window = cfg.sliding_window
    new_len = cache["len"] + 1
    x = layers.embed(params["embed"], cfg, token).astype(cfg.compute_dtype)
    # decode position = new_len - 1, evaluated per slot: a shared scalar
    # ``len`` broadcasts over B, a per-slot (B,) vector (the slot engine /
    # paged arena case) gives every slot its own position row
    pos = jnp.asarray(new_len - 1, jnp.float32)
    if pos.ndim == 0:
        pos = jnp.full((B,), pos)
    x = x + layers.sinusoid_at(pos, cfg.d_model).astype(x.dtype)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i, ck, cv = xs
        x = constrain_activation(x)
        kc = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        S = kc.shape[1]
        eff_window = None if (window is None or S <= window) else window
        xn = layers.apply_norm(lp["ln1"], cfg, x[:, None])[:, 0]
        a, kc, vc = layers.attention_decode(lp["self_attn"], cfg, xn, kc, vc,
                                            new_len, window=eff_window,
                                            use_rope=False, impl=impl)
        x = x + a
        xn = layers.apply_norm(lp["ln_x"], cfg, x[:, None])[:, 0]
        q = layers.linear(xn, lp["cross_attn"]["wq"]).reshape(
            B, cfg.num_heads, cfg.head_dim)
        c = ops.decode_attention(q, ck, cv, ck.shape[1], impl=impl)
        c = layers.linear(c.reshape(B, -1), lp["cross_attn"]["wo"])
        x = x + c
        xn = layers.apply_norm(lp["ln2"], cfg, x[:, None])[:, 0]
        x = x + layers.mlp(lp["mlp"], cfg, xn)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, i, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, i, 0)
        return (x, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["dec_blocks"], jnp.arange(cfg.num_layers),
         cache["cross_k"], cache["cross_v"]))
    h = layers.apply_norm(params["ln_f"], cfg, x[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "cross_k": cache["cross_k"],
                    "cross_v": cache["cross_v"], "len": new_len}


def decode_step_paged(params, cfg: ModelConfig, token, cache, block_tables,
                      live, *, block_size, impl=None):
    """Paged-native fused decode: decoder self-attention streams K/V
    through the block table and writes one row per live slot; the fixed
    encoder cross-K/V ride along as per-slot state exactly as in
    ``decode_step``."""
    B = token.shape[0]
    lens = jnp.asarray(cache["len"], jnp.int32)
    live = jnp.asarray(live, bool)
    x = layers.embed(params["embed"], cfg, token).astype(cfg.compute_dtype)
    # decode position = lens (per-slot), matching decode_step's new_len - 1
    x = x + layers.sinusoid_at(lens.astype(jnp.float32),
                               cfg.d_model).astype(x.dtype)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i, ck, cv = xs
        x = constrain_activation(x)
        xn = layers.apply_norm(lp["ln1"], cfg, x[:, None])[:, 0]
        a, k_all, v_all = layers.attention_decode_paged(
            lp["self_attn"], cfg, xn, k_all, v_all, block_tables, lens, live,
            block_size=block_size, layer=i, window=cfg.sliding_window,
            use_rope=False, impl=impl)
        x = x + a
        xn = layers.apply_norm(lp["ln_x"], cfg, x[:, None])[:, 0]
        q = layers.linear(xn, lp["cross_attn"]["wq"]).reshape(
            B, cfg.num_heads, cfg.head_dim)
        c = ops.decode_attention(q, ck, cv, ck.shape[1], impl=impl)
        c = layers.linear(c.reshape(B, -1), lp["cross_attn"]["wo"])
        x = x + c
        xn = layers.apply_norm(lp["ln2"], cfg, x[:, None])[:, 0]
        x = x + layers.mlp(lp["mlp"], cfg, xn)
        return (x, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["dec_blocks"], jnp.arange(cfg.num_layers),
         cache["cross_k"], cache["cross_v"]))
    h = layers.apply_norm(params["ln_f"], cfg, x[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "cross_k": cache["cross_k"],
                    "cross_v": cache["cross_v"],
                    "len": jnp.where(live, lens + 1, lens)}
