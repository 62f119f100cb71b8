"""PaliGemma-3B LANGUAGE BACKBONE (gemma-2b decoder + image-prefix).

The SigLIP vision tower + projector are a STUB per the assignment
carve-out: ``input_specs`` feeds precomputed patch embeddings
(B, prefix_len, d_model).  This module implements the gemma-style decoder
(MQA kv=1, head_dim 256, geglu, tied embeddings) with PaliGemma's
prefix-LM masking: bidirectional attention over the image prefix, causal
over text.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import layers, transformer
from .config import ModelConfig
from .sharding import constrain_activation


init = transformer.init          # same param structure as a dense decoder
init_block = transformer.init_block
logits_fn = transformer.logits_fn
init_cache = transformer.init_cache


def _concat_inputs(params, cfg: ModelConfig, batch):
    img = batch["embeddings"].astype(cfg.compute_dtype)  # (B, P, d)
    tok = layers.embed(params["embed"], cfg,
                       batch["tokens"]).astype(cfg.compute_dtype)
    return jnp.concatenate([img, tok], axis=1)


def forward_hidden(params, cfg: ModelConfig, batch: Dict[str, Any], *,
                   train: bool = False, impl=None):
    """Returns hidden states for the FULL (prefix + text) sequence; the
    training loss masks the prefix region."""
    h = _concat_inputs(params, cfg, batch)
    B, L, _ = h.shape
    positions = jnp.arange(L)[None]
    prefix = cfg.prefix_len

    def body(carry, lp):
        out = transformer.block_forward(lp, cfg, carry, positions=positions,
                                        window=cfg.sliding_window,
                                        prefix_len=prefix, impl=impl)
        return out, None

    scan_body = jax.checkpoint(body) if train else body
    h, _ = jax.lax.scan(scan_body, h, params["blocks"])
    h = layers.apply_norm(params["ln_f"], cfg, h)
    return h, jnp.zeros((), jnp.float32)


def prefill(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            cache_size: Optional[int] = None, impl=None):
    h = _concat_inputs(params, cfg, batch)
    B, L, _ = h.shape            # L includes the image prefix
    window = cfg.sliding_window
    # callers budget cache_size in TEXT tokens; the image prefix rides along
    cache_size = (cache_size + cfg.prefix_len) if cache_size else L
    if window is not None:
        cache_size = min(cache_size, window)
    else:
        cache_size = max(cache_size, L)  # full attention never trims
    positions = jnp.arange(L)[None]

    def body(carry, lp):
        out, kv = transformer.block_prefill(
            lp, cfg, carry, positions=positions, window=window,
            prefix_len=cfg.prefix_len, cache_size=cache_size, impl=impl)
        return out, kv

    h, (k, v) = jax.lax.scan(body, h, params["blocks"])
    h = layers.apply_norm(params["ln_f"], cfg, h[:, -1:])
    logits = logits_fn(params, cfg, h[:, 0])
    return logits, {"k": k, "v": v, "len": jnp.asarray(L, jnp.int32)}


def prefill_chunk(params, cfg: ModelConfig, batch, cache, *, chunk_len,
                  impl=None):
    """Chunked prefill.  The FIRST chunk carries ``batch["embeddings"]``
    and processes the whole image prefix together with the first text
    bucket (prefix-LM bidirectionality makes the prefix indivisible:
    prefix rows attend to later prefix rows, so the prefix cannot span a
    chunk boundary).  Later chunks are plain causal text appends — every
    cached position (prefix included) is attendable, as in decode."""
    first = "embeddings" in batch
    if first:
        h = _concat_inputs(params, cfg, batch)     # (B, P + T, d)
        prefix = cfg.prefix_len
    else:
        h = layers.embed(params["embed"], cfg,
                         batch["tokens"]).astype(cfg.compute_dtype)
        prefix = 0
    eff_chunk = chunk_len + prefix                 # cache rows written
    window = cfg.sliding_window
    start = cache["len"]

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        kc = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        x, kc, vc = transformer.block_prefill_chunk(
            lp, cfg, x, kc, vc, start, eff_chunk, window=window,
            prefix_len=prefix, impl=impl)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, i, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, i, 0)
        return (x, k_all, v_all), None

    (h, k, v), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.take_chunk_last(h, eff_chunk)
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "len": cache["len"] + eff_chunk}


def prefill_chunk_paged(params, cfg: ModelConfig, batch, cache,
                        block_tables, *, chunk_len, block_size, impl=None):
    """Paged-native chunked prefill (see ``prefill_chunk``): the first
    chunk carries the whole bidirectional image prefix, and every written
    row — prefix and text alike — scatters straight into the arena page
    pools through the block table."""
    first = "embeddings" in batch
    if first:
        h = _concat_inputs(params, cfg, batch)     # (B, P + T, d)
        prefix = cfg.prefix_len
    else:
        h = layers.embed(params["embed"], cfg,
                         batch["tokens"]).astype(cfg.compute_dtype)
        prefix = 0
    eff_chunk = chunk_len + prefix                 # cache rows written
    window = cfg.sliding_window
    start = jnp.asarray(cache["len"], jnp.int32).reshape(-1)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        x = constrain_activation(x)
        xn = layers.apply_norm(lp["ln1"], cfg, x)
        a, k_all, v_all = layers.attention_chunk_paged(
            lp["attn"], cfg, xn, k_all, v_all, block_tables, start, eff_chunk,
            block_size=block_size, layer=i, window=window, prefix_len=prefix,
            impl=impl)
        x = x + a
        x = x + layers.mlp(lp["mlp"], cfg,
                           layers.apply_norm(lp["ln2"], cfg, x))
        return (x, k_all, v_all), None

    (h, k, v), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.take_chunk_last(h, eff_chunk)
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "len": start + eff_chunk}


# decode: after prefill every cached position is attendable by new tokens
# (prefix bidirectionality only affects prefix-internal rows, which are
# already baked into the cache), so dense decode semantics apply directly
# — for the paged layout too.
decode_step = transformer.decode_step
decode_step_paged = transformer.decode_step_paged
