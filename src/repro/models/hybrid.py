"""Zamba2-style hybrid: Mamba-2 backbone with a single SHARED attention
block applied every ``attn_every`` SSM layers (zamba2-7b).

The shared block consumes concat(h, h0) (h0 = the original embeddings, the
Zamba trick) through one weight set reused at every application point, but
each application keeps its own KV cache.  Layer structure is a scan over
``n_apps`` groups of (attn_every mamba layers + shared attention), plus a
scanned tail of leftover mamba layers — HLO stays O(1) in depth.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import layers, ssm, transformer
from .config import ModelConfig
from .sharding import constrain_activation


def _n_apps(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.attn_every


def _tail_layers(cfg: ModelConfig) -> int:
    return cfg.num_layers - _n_apps(cfg) * cfg.attn_every


def init_shared_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "ln_a": layers.init_norm(ks[0], cfg, dim=2 * cfg.d_model),
        "attn": layers.init_attention(ks[1], cfg, d_in=2 * cfg.d_model),
        "ln_m": layers.init_norm(ks[2], cfg),
        "mlp": layers.init_mlp(ks[3], cfg),
    }


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "embed": layers.init_embedding(ks[0], cfg),
        "mamba": transformer.stack_layer_params(
            ks[1], cfg.num_layers, lambda k: ssm.init_mamba_block(k, cfg)),
        "shared": init_shared_block(ks[2], cfg),
        "ln_f": layers.init_norm(ks[3], cfg),
    }


def _split_groups(cfg: ModelConfig, stacked):
    napps, every = _n_apps(cfg), cfg.attn_every
    head = jax.tree.map(
        lambda a: a[:napps * every].reshape(napps, every, *a.shape[1:]),
        stacked)
    tail = jax.tree.map(lambda a: a[napps * every:], stacked)
    return head, tail


def _shared_forward(shared, cfg: ModelConfig, h, h0, *, positions, window,
                    collect_kv: bool, cache_size: int = 0, impl=None):
    h = constrain_activation(h)
    xcat = jnp.concatenate([h, h0], axis=-1)
    xn = layers.apply_norm(shared["ln_a"], cfg, xcat)
    a, (k, v) = layers.attention(shared["attn"], cfg, xn, positions=positions,
                                 causal=True, window=window, impl=impl)
    h = h + a
    h = h + layers.mlp(shared["mlp"], cfg,
                       layers.apply_norm(shared["ln_m"], cfg, h))
    if not collect_kv:
        return h, None
    L = k.shape[1]
    if cache_size > L:
        pad = ((0, 0), (0, cache_size - L), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    elif cache_size and cache_size < L:
        k, v = k[:, L - cache_size:], v[:, L - cache_size:]
        shift = L % cache_size
        k, v = jnp.roll(k, shift, axis=1), jnp.roll(v, shift, axis=1)
    return h, (k, v)


def _shared_decode(shared, cfg: ModelConfig, h_t, h0_t, k_cache, v_cache,
                   cache_len, *, window, impl=None):
    S = k_cache.shape[1]
    eff_window = None if (window is None or S <= window) else window
    xcat = jnp.concatenate([h_t, h0_t], axis=-1)
    xn = layers.apply_norm(shared["ln_a"], cfg, xcat[:, None])[:, 0]
    a, k_cache, v_cache = layers.attention_decode(
        shared["attn"], cfg, xn, k_cache, v_cache, cache_len,
        window=eff_window, impl=impl)
    h_t = h_t + a
    xn = layers.apply_norm(shared["ln_m"], cfg, h_t[:, None])[:, 0]
    h_t = h_t + layers.mlp(shared["mlp"], cfg, xn)
    return h_t, k_cache, v_cache


def _shared_chunk(shared, cfg: ModelConfig, h, h0, k_cache, v_cache,
                  cache_len, chunk_len, *, window, impl=None):
    """Chunked-prefill pass through the shared attention block (multi-token
    sibling of ``_shared_decode``)."""
    h = constrain_activation(h)
    xcat = jnp.concatenate([h, h0], axis=-1)
    xn = layers.apply_norm(shared["ln_a"], cfg, xcat)
    a, k_cache, v_cache = layers.attention_chunk(
        shared["attn"], cfg, xn, k_cache, v_cache, cache_len, chunk_len,
        window=window, impl=impl)
    h = h + a
    h = h + layers.mlp(shared["mlp"], cfg,
                       layers.apply_norm(shared["ln_m"], cfg, h))
    return h, k_cache, v_cache


def _shared_decode_paged(shared, cfg: ModelConfig, h_t, h0_t, k_pages,
                         v_pages, block_tables, lens, live, *, block_size,
                         layer, window, impl=None):
    """Paged-native ``_shared_decode``: application ``layer``'s K/V stream
    through the block table out of the stacked pools, only the new row is
    written back."""
    xcat = jnp.concatenate([h_t, h0_t], axis=-1)
    xn = layers.apply_norm(shared["ln_a"], cfg, xcat[:, None])[:, 0]
    a, k_pages, v_pages = layers.attention_decode_paged(
        shared["attn"], cfg, xn, k_pages, v_pages, block_tables, lens,
        live, block_size=block_size, layer=layer, window=window, impl=impl)
    h_t = h_t + a
    xn = layers.apply_norm(shared["ln_m"], cfg, h_t[:, None])[:, 0]
    h_t = h_t + layers.mlp(shared["mlp"], cfg, xn)
    return h_t, k_pages, v_pages


def _shared_chunk_paged(shared, cfg: ModelConfig, h, h0, k_pages, v_pages,
                        block_tables, cache_len, chunk_len, *, block_size,
                        layer, window, impl=None):
    """Paged-native ``_shared_chunk``."""
    h = constrain_activation(h)
    xcat = jnp.concatenate([h, h0], axis=-1)
    xn = layers.apply_norm(shared["ln_a"], cfg, xcat)
    a, k_pages, v_pages = layers.attention_chunk_paged(
        shared["attn"], cfg, xn, k_pages, v_pages, block_tables, cache_len,
        chunk_len, block_size=block_size, layer=layer, window=window,
        impl=impl)
    h = h + a
    h = h + layers.mlp(shared["mlp"], cfg,
                       layers.apply_norm(shared["ln_m"], cfg, h))
    return h, k_pages, v_pages


# ---------------------------------------------------------------------------
# model API
# ---------------------------------------------------------------------------

def forward_hidden(params, cfg: ModelConfig, batch: Dict[str, Any], *,
                   train: bool = False, impl=None):
    tokens = batch["tokens"]
    B, L = tokens.shape
    h0 = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    positions = jnp.arange(L)[None]
    head, tail = _split_groups(cfg, params["mamba"])
    window = cfg.sliding_window

    def mamba_body(carry, lp):
        return ssm.mamba_block(lp, cfg, carry, impl=impl), None

    mb = jax.checkpoint(mamba_body) if train else mamba_body

    def group_body(carry, group_params):
        h, _ = jax.lax.scan(mb, carry, group_params)
        h, _ = _shared_forward(params["shared"], cfg, h, h0,
                               positions=positions, window=window,
                               collect_kv=False, impl=impl)
        return h, None

    h, _ = jax.lax.scan(group_body, h0, head)
    if _tail_layers(cfg):
        h, _ = jax.lax.scan(mb, h, tail)
    h = layers.apply_norm(params["ln_f"], cfg, h)
    return h, jnp.zeros((), jnp.float32)


def logits_fn(params, cfg: ModelConfig, hidden):
    return layers.unembed(params["embed"], cfg, hidden)


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int, dtype=None):
    dtype = dtype or cfg.compute_dtype
    base = ssm.init_cache(cfg, batch_size, max_len, dtype)
    window = cfg.sliding_window
    S = min(max_len, window) if window is not None else max_len
    kv_shape = (_n_apps(cfg), batch_size, S, cfg.num_kv_heads, cfg.head_dim)
    base["attn_k"] = jnp.zeros(kv_shape, dtype)
    base["attn_v"] = jnp.zeros(kv_shape, dtype)
    return base


def prefill(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            cache_size: Optional[int] = None, impl=None):
    tokens = batch["tokens"]
    B, L = tokens.shape
    window = cfg.sliding_window
    kv_size = cache_size or L
    if window is not None:
        kv_size = min(kv_size, window)
    else:
        kv_size = max(kv_size, L)  # full attention never trims
    h0 = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    positions = jnp.arange(L)[None]
    head, tail = _split_groups(cfg, params["mamba"])

    def mamba_body(carry, lp):
        out, (tail_s, state) = ssm.mamba_block(lp, cfg, carry,
                                               return_state=True, impl=impl)
        return out, (tail_s, state)

    def group_body(carry, group_params):
        h, states = jax.lax.scan(mamba_body, carry, group_params)
        h, kv = _shared_forward(params["shared"], cfg, h, h0,
                                positions=positions, window=window,
                                collect_kv=True, cache_size=kv_size,
                                impl=impl)
        return h, (states, kv)

    h, (gstates, (ak, av)) = jax.lax.scan(group_body, h0, head)
    conv = gstates[0].reshape(-1, *gstates[0].shape[2:])
    ssd = gstates[1].reshape(-1, *gstates[1].shape[2:])
    if _tail_layers(cfg):
        h, (tconv, tssd) = jax.lax.scan(mamba_body, h, tail)
        conv = jnp.concatenate([conv, tconv], axis=0)
        ssd = jnp.concatenate([ssd, tssd], axis=0)
    h = layers.apply_norm(params["ln_f"], cfg, h[:, -1:])
    logits = logits_fn(params, cfg, h[:, 0])
    cache = {"conv": conv, "ssd": ssd, "attn_k": ak, "attn_v": av,
             "len": jnp.asarray(L, jnp.int32)}
    return logits, cache


def prefill_chunk(params, cfg: ModelConfig, batch, cache, *, chunk_len,
                  impl=None):
    """Chunked prefill: mamba layers advance their recurrent state via
    ``ssm.mamba_block_chunk``; each shared-attention application appends
    the chunk's K/V to its own cache row (same carry-DUS layout as
    ``decode_step``, with a T-token block instead of one token)."""
    tokens = batch["tokens"]
    window = cfg.sliding_window
    h0 = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    napps, every = _n_apps(cfg), cfg.attn_every
    n_head = napps * every
    head, tail = _split_groups(cfg, params["mamba"])
    start = cache["len"]

    def mamba_body(carry, xs):
        h, conv_all, ssd_all = carry
        lp, i = xs
        conv = jax.lax.dynamic_index_in_dim(conv_all, i, 0, keepdims=False)
        ssd = jax.lax.dynamic_index_in_dim(ssd_all, i, 0, keepdims=False)
        h, conv, ssd = ssm.mamba_block_chunk(lp, cfg, h, conv, ssd,
                                             chunk_len, impl=impl)
        conv_all = jax.lax.dynamic_update_index_in_dim(
            conv_all, conv.astype(conv_all.dtype), i, 0)
        ssd_all = jax.lax.dynamic_update_index_in_dim(
            ssd_all, ssd.astype(ssd_all.dtype), i, 0)
        return (h, conv_all, ssd_all), None

    def group_body(carry, xs):
        h, conv_all, ssd_all, k_all, v_all = carry
        gp, g = xs
        idx = g * every + jnp.arange(every)
        (h, conv_all, ssd_all), _ = jax.lax.scan(
            mamba_body, (h, conv_all, ssd_all), (gp, idx))
        kc = jax.lax.dynamic_index_in_dim(k_all, g, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, g, 0, keepdims=False)
        h, kc, vc = _shared_chunk(params["shared"], cfg, h, h0, kc, vc,
                                  start, chunk_len, window=window,
                                  impl=impl)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, g, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, g, 0)
        return (h, conv_all, ssd_all, k_all, v_all), None

    carry0 = (h0, cache["conv"], cache["ssd"], cache["attn_k"],
              cache["attn_v"])
    (h, conv, ssd, ak, av), _ = jax.lax.scan(
        group_body, carry0, (head, jnp.arange(napps)))
    if _tail_layers(cfg):
        tail_idx = n_head + jnp.arange(_tail_layers(cfg))
        (h, conv, ssd), _ = jax.lax.scan(
            mamba_body, (h, conv, ssd), (tail, tail_idx))
    h = layers.take_chunk_last(h, chunk_len)
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"conv": conv, "ssd": ssd, "attn_k": ak, "attn_v": av,
                    "len": cache["len"] + chunk_len}


def prefill_chunk_paged(params, cfg: ModelConfig, batch, cache,
                        block_tables, *, chunk_len, block_size, impl=None):
    """Paged-native chunked prefill: mamba conv/SSD state advances exactly
    as in ``prefill_chunk`` (per-slot state is never paged); each shared-
    attention application scatters its chunk K/V rows straight into its
    arena page pool through the block table."""
    tokens = batch["tokens"]
    window = cfg.sliding_window
    h0 = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    napps, every = _n_apps(cfg), cfg.attn_every
    n_head = napps * every
    head, tail = _split_groups(cfg, params["mamba"])
    start = jnp.asarray(cache["len"], jnp.int32).reshape(-1)

    def mamba_body(carry, xs):
        h, conv_all, ssd_all = carry
        lp, i = xs
        conv = jax.lax.dynamic_index_in_dim(conv_all, i, 0, keepdims=False)
        ssd = jax.lax.dynamic_index_in_dim(ssd_all, i, 0, keepdims=False)
        h, conv, ssd = ssm.mamba_block_chunk(lp, cfg, h, conv, ssd,
                                             chunk_len, impl=impl)
        conv_all = jax.lax.dynamic_update_index_in_dim(
            conv_all, conv.astype(conv_all.dtype), i, 0)
        ssd_all = jax.lax.dynamic_update_index_in_dim(
            ssd_all, ssd.astype(ssd_all.dtype), i, 0)
        return (h, conv_all, ssd_all), None

    def group_body(carry, xs):
        h, conv_all, ssd_all, k_all, v_all = carry
        gp, g = xs
        idx = g * every + jnp.arange(every)
        (h, conv_all, ssd_all), _ = jax.lax.scan(
            mamba_body, (h, conv_all, ssd_all), (gp, idx))
        h, k_all, v_all = _shared_chunk_paged(
            params["shared"], cfg, h, h0, k_all, v_all, block_tables, start,
            chunk_len, block_size=block_size, layer=g, window=window,
            impl=impl)
        return (h, conv_all, ssd_all, k_all, v_all), None

    carry0 = (h0, cache["conv"], cache["ssd"], cache["attn_k"],
              cache["attn_v"])
    (h, conv, ssd, ak, av), _ = jax.lax.scan(
        group_body, carry0, (head, jnp.arange(napps)))
    if _tail_layers(cfg):
        tail_idx = n_head + jnp.arange(_tail_layers(cfg))
        (h, conv, ssd), _ = jax.lax.scan(
            mamba_body, (h, conv, ssd), (tail, tail_idx))
    h = layers.take_chunk_last(h, chunk_len)
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"conv": conv, "ssd": ssd, "attn_k": ak, "attn_v": av,
                    "len": start + chunk_len}


def decode_step(params, cfg: ModelConfig, token, cache, impl=None):
    """Carry-DUS cache updates throughout (see transformer.decode_step):
    mamba conv/ssd states indexed by the FLAT layer id, shared-attention
    caches by the application id — everything stays in one donated buffer."""
    window = cfg.sliding_window
    new_len = cache["len"] + 1
    h0 = layers.embed(params["embed"], cfg, token).astype(cfg.compute_dtype)
    napps, every = _n_apps(cfg), cfg.attn_every
    n_head = napps * every
    head, tail = _split_groups(cfg, params["mamba"])

    def mamba_body(carry, xs):
        h, conv_all, ssd_all = carry
        lp, i = xs
        conv = jax.lax.dynamic_index_in_dim(conv_all, i, 0, keepdims=False)
        ssd = jax.lax.dynamic_index_in_dim(ssd_all, i, 0, keepdims=False)
        h, conv, ssd = ssm.mamba_block_decode(lp, cfg, h, conv, ssd,
                                              impl=impl)
        conv_all = jax.lax.dynamic_update_index_in_dim(conv_all, conv, i, 0)
        ssd_all = jax.lax.dynamic_update_index_in_dim(
            ssd_all, ssd.astype(ssd_all.dtype), i, 0)
        return (h, conv_all, ssd_all), None

    def group_body(carry, xs):
        h, conv_all, ssd_all, k_all, v_all = carry
        gp, g = xs
        idx = g * every + jnp.arange(every)
        (h, conv_all, ssd_all), _ = jax.lax.scan(
            mamba_body, (h, conv_all, ssd_all), (gp, idx))
        kc = jax.lax.dynamic_index_in_dim(k_all, g, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, g, 0, keepdims=False)
        h, kc, vc = _shared_decode(params["shared"], cfg, h, h0, kc, vc,
                                   new_len, window=window, impl=impl)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, g, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, g, 0)
        return (h, conv_all, ssd_all, k_all, v_all), None

    carry0 = (h0, cache["conv"], cache["ssd"], cache["attn_k"],
              cache["attn_v"])
    (h, conv, ssd, ak, av), _ = jax.lax.scan(
        group_body, carry0, (head, jnp.arange(napps)))
    if _tail_layers(cfg):
        tail_idx = n_head + jnp.arange(_tail_layers(cfg))
        (h, conv, ssd), _ = jax.lax.scan(
            mamba_body, (h, conv, ssd), (tail, tail_idx))
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"conv": conv, "ssd": ssd, "attn_k": ak, "attn_v": av,
                    "len": new_len}


def decode_step_paged(params, cfg: ModelConfig, token, cache, block_tables,
                      live, *, block_size, impl=None):
    """Paged-native fused decode: the mamba backbone's conv/SSD state is
    untouched (state side-channel), each shared-attention application
    streams its K/V through the block table and writes one new row per
    live slot."""
    window = cfg.sliding_window
    lens = jnp.asarray(cache["len"], jnp.int32)
    live = jnp.asarray(live, bool)
    h0 = layers.embed(params["embed"], cfg, token).astype(cfg.compute_dtype)
    napps, every = _n_apps(cfg), cfg.attn_every
    n_head = napps * every
    head, tail = _split_groups(cfg, params["mamba"])

    def mamba_body(carry, xs):
        h, conv_all, ssd_all = carry
        lp, i = xs
        conv = jax.lax.dynamic_index_in_dim(conv_all, i, 0, keepdims=False)
        ssd = jax.lax.dynamic_index_in_dim(ssd_all, i, 0, keepdims=False)
        h, conv, ssd = ssm.mamba_block_decode(lp, cfg, h, conv, ssd,
                                              impl=impl)
        conv_all = jax.lax.dynamic_update_index_in_dim(conv_all, conv, i, 0)
        ssd_all = jax.lax.dynamic_update_index_in_dim(
            ssd_all, ssd.astype(ssd_all.dtype), i, 0)
        return (h, conv_all, ssd_all), None

    def group_body(carry, xs):
        h, conv_all, ssd_all, k_all, v_all = carry
        gp, g = xs
        idx = g * every + jnp.arange(every)
        (h, conv_all, ssd_all), _ = jax.lax.scan(
            mamba_body, (h, conv_all, ssd_all), (gp, idx))
        h, k_all, v_all = _shared_decode_paged(
            params["shared"], cfg, h, h0, k_all, v_all, block_tables, lens,
            live, block_size=block_size, layer=g, window=window, impl=impl)
        return (h, conv_all, ssd_all, k_all, v_all), None

    carry0 = (h0, cache["conv"], cache["ssd"], cache["attn_k"],
              cache["attn_v"])
    (h, conv, ssd, ak, av), _ = jax.lax.scan(
        group_body, carry0, (head, jnp.arange(napps)))
    if _tail_layers(cfg):
        tail_idx = n_head + jnp.arange(_tail_layers(cfg))
        (h, conv, ssd), _ = jax.lax.scan(
            mamba_body, (h, conv, ssd), (tail, tail_idx))
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"conv": conv, "ssd": ssd, "attn_k": ak, "attn_v": av,
                    "len": jnp.where(live, lens + 1, lens)}
