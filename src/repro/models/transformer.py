"""Dense decoder-only transformer (llama/mistral/qwen/minicpm families).

Layer-stacked parameters + ``jax.lax.scan`` over layers keep the HLO size
O(1) in depth (88-layer configs would otherwise blow up lowering time for
the 40-combo dry-run).  Supports GQA/MQA/MHA, optional sliding window
(native for mixtral-style cfgs, or the explicit long-context variant), and
prefix-LM masking (used by the VLM wrapper).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


from . import layers
from .config import ModelConfig
from .sharding import constrain_activation


def stack_layer_params(key, n: int, init_fn):
    """``n`` layers' params stacked on a leading axis.  ``vmap`` over the
    per-layer keys draws the same values as initialising layer by layer,
    but writes the stack directly — no per-layer copies to concatenate,
    so a jitted init peaks at the parameters' own size."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "ln1": layers.init_norm(ks[0], cfg),
        "attn": layers.init_attention(ks[1], cfg),
        "ln2": layers.init_norm(ks[2], cfg),
        "mlp": layers.init_mlp(ks[3], cfg),
    }


def block_forward(p, cfg: ModelConfig, x, *, positions, window, prefix_len,
                  impl=None):
    x = constrain_activation(x)
    h, _ = layers.attention(p["attn"], cfg, layers.apply_norm(p["ln1"], cfg, x),
                            positions=positions, causal=True, window=window,
                            prefix_len=prefix_len, impl=impl)
    x = x + h
    x = x + layers.mlp(p["mlp"], cfg, layers.apply_norm(p["ln2"], cfg, x))
    return x


def block_prefill(p, cfg: ModelConfig, x, *, positions, window, prefix_len,
                  cache_size, impl=None):
    x = constrain_activation(x)
    xn = layers.apply_norm(p["ln1"], cfg, x)
    h, (k, v) = layers.attention(p["attn"], cfg, xn, positions=positions,
                                 causal=True, window=window,
                                 prefix_len=prefix_len, impl=impl)
    x = x + h
    x = x + layers.mlp(p["mlp"], cfg, layers.apply_norm(p["ln2"], cfg, x))
    L = k.shape[1]
    if cache_size > L:
        pad = ((0, 0), (0, cache_size - L), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    elif cache_size < L:  # ring cache (SWA): keep the trailing window,
        # laid out so position p sits at ring slot p % cache_size (decode
        # writes token at slot (len-1) % S, so layouts must agree).
        k, v = k[:, L - cache_size:], v[:, L - cache_size:]
        shift = L % cache_size
        k = jnp.roll(k, shift, axis=1)
        v = jnp.roll(v, shift, axis=1)
    return x, (k, v)


def block_prefill_chunk(p, cfg: ModelConfig, x, k_cache, v_cache, cache_len,
                        chunk_len, *, window, prefix_len=0, impl=None):
    """Chunked-prefill block: append a T-token chunk to one layer's cache
    (per-slot ``cache_len``) and attend causally over everything written so
    far.  The multi-token sibling of ``block_decode``."""
    x = constrain_activation(x)
    xn = layers.apply_norm(p["ln1"], cfg, x)
    h, k_cache, v_cache = layers.attention_chunk(
        p["attn"], cfg, xn, k_cache, v_cache, cache_len, chunk_len,
        window=window, prefix_len=prefix_len, impl=impl)
    x = x + h
    x = x + layers.mlp(p["mlp"], cfg, layers.apply_norm(p["ln2"], cfg, x))
    return x, k_cache, v_cache


def block_decode(p, cfg: ModelConfig, x_t, k_cache, v_cache, cache_len, *,
                 window, impl=None):
    x_t = constrain_activation(x_t)
    S = k_cache.shape[1]
    eff_window = None if (window is None or S <= window) else window
    xn = layers.apply_norm(p["ln1"], cfg, x_t[:, None])[:, 0]
    h, k_cache, v_cache = layers.attention_decode(
        p["attn"], cfg, xn, k_cache, v_cache, cache_len,
        window=eff_window, impl=impl)
    x_t = x_t + h
    xn = layers.apply_norm(p["ln2"], cfg, x_t[:, None])[:, 0]
    x_t = x_t + layers.mlp(p["mlp"], cfg, xn)
    return x_t, k_cache, v_cache


# ---------------------------------------------------------------------------
# model API
# ---------------------------------------------------------------------------

def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    return {
        "embed": layers.init_embedding(ks[0], cfg),
        "blocks": stack_layer_params(ks[1], cfg.num_layers,
                                     lambda k: init_block(k, cfg)),
        "ln_f": layers.init_norm(ks[2], cfg),
    }


def _window(cfg: ModelConfig) -> Optional[int]:
    return cfg.sliding_window


def forward_hidden(params, cfg: ModelConfig, batch: Dict[str, Any], *,
                   train: bool = False, impl=None):
    tokens = batch["tokens"]
    B, L = tokens.shape
    h = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    positions = jnp.arange(L)[None]
    window = _window(cfg)

    def body(carry, lp):
        out = block_forward(lp, cfg, carry, positions=positions,
                            window=window, prefix_len=0, impl=impl)
        return out, None

    scan_body = jax.checkpoint(body) if train else body
    h, _ = jax.lax.scan(scan_body, h, params["blocks"])
    h = layers.apply_norm(params["ln_f"], cfg, h)
    return h, jnp.zeros((), jnp.float32)  # (hidden, aux_loss)


def logits_fn(params, cfg: ModelConfig, hidden):
    return layers.unembed(params["embed"], cfg, hidden)


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               dtype=None) -> Dict[str, Any]:
    dtype = dtype or cfg.compute_dtype
    window = _window(cfg)
    S = min(max_len, window) if window is not None else max_len
    shape = (cfg.num_layers, batch_size, S, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "len": jnp.zeros((), jnp.int32)}


def prefill(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            cache_size: Optional[int] = None, impl=None):
    tokens = batch["tokens"]
    B, L = tokens.shape
    window = _window(cfg)
    cache_size = cache_size or L
    if window is not None:
        cache_size = min(cache_size, window)
    else:
        cache_size = max(cache_size, L)  # full attention never trims
    h = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    positions = jnp.arange(L)[None]

    def body(carry, lp):
        out, kv = block_prefill(lp, cfg, carry, positions=positions,
                                window=window, prefix_len=0,
                                cache_size=cache_size, impl=impl)
        return out, kv

    h, (k, v) = jax.lax.scan(body, h, params["blocks"])
    h = layers.apply_norm(params["ln_f"], cfg, h[:, -1:])
    logits = logits_fn(params, cfg, h[:, 0])
    cache = {"k": k, "v": v, "len": jnp.asarray(L, jnp.int32)}
    return logits, cache


def prefill_chunk(params, cfg: ModelConfig, batch, cache, *, chunk_len,
                  impl=None):
    """Chunked (piggybacked) prefill: append a right-padded chunk of
    ``chunk_len`` <= T prompt tokens to an existing cache whose ``len``
    counts tokens already written (0 for the first chunk).

    Chaining chunks over a prompt is numerically equivalent to one-shot
    ``prefill`` — same absolute rope positions, same causal visibility —
    but every call runs at the STATIC bucket shape (B, T), so the serving
    engine compiles one trace per chunk bucket instead of one per prompt
    length.  Returns (logits at the chunk's last real token, new cache);
    ``chunk_len`` may be a traced scalar.
    """
    tokens = batch["tokens"]
    window = _window(cfg)
    x = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    start = cache["len"]

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        kc = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        x, kc, vc = block_prefill_chunk(lp, cfg, x, kc, vc, start,
                                        chunk_len, window=window, impl=impl)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, i, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, i, 0)
        return (x, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.take_chunk_last(x, chunk_len)
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "len": cache["len"] + chunk_len}


def prefill_chunk_paged(params, cfg: ModelConfig, batch, cache,
                        block_tables, *, chunk_len, block_size, impl=None):
    """Paged-native chunked prefill: the cache's ``k``/``v`` are the
    arena's stacked PAGE POOLS (``kernels.paged_pool`` layout) read
    through ``block_tables`` (B, nblk), and ``len`` is the per-slot (B,)
    start offset.  Each layer reads and writes the stacked pools at its
    own index: the chunk's K/V rows scatter straight into the pages
    (``layers.attention_chunk_paged``) — no layer slice, dense view or
    re-scatter.  Numerically equivalent to ``prefill_chunk`` on the
    gathered view."""
    tokens = batch["tokens"]
    window = _window(cfg)
    x = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    start = jnp.asarray(cache["len"], jnp.int32).reshape(-1)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        x = constrain_activation(x)
        xn = layers.apply_norm(lp["ln1"], cfg, x)
        h, k_all, v_all = layers.attention_chunk_paged(
            lp["attn"], cfg, xn, k_all, v_all, block_tables, start, chunk_len,
            block_size=block_size, layer=i, window=window, impl=impl)
        x = x + h
        x = x + layers.mlp(lp["mlp"], cfg,
                           layers.apply_norm(lp["ln2"], cfg, x))
        return (x, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.take_chunk_last(x, chunk_len)
    h = layers.apply_norm(params["ln_f"], cfg, h[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "len": start + chunk_len}


def verify_step_paged(params, cfg: ModelConfig, batch, cache, block_tables,
                      *, chunk_len, block_size, impl=None):
    """Speculative-decoding verify: score T = k+1 fed tokens
    ``[last_emitted, d_1 .. d_k]`` against the paged cache in ONE fused
    launch and return logits for ALL T positions ``(B, T, V)`` — the same
    chunk-attention body as ``prefill_chunk_paged`` (K/V rows scatter in
    place through the block tables; ``chunk_len`` is a per-slot (B,)
    vector, 0 for non-speculating rows whose writes route to the trash
    block), but the head runs over the full chunk instead of
    ``take_chunk_last``.  ``cache['len']`` is returned UNCHANGED: the
    engine's verifier commits lengths only after acceptance, so rejected
    draft rows are garbage past ``len`` that the next round overwrites."""
    tokens = batch["tokens"]
    window = _window(cfg)
    x = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    start = jnp.asarray(cache["len"], jnp.int32).reshape(-1)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        x = constrain_activation(x)
        xn = layers.apply_norm(lp["ln1"], cfg, x)
        h, k_all, v_all = layers.attention_chunk_paged(
            lp["attn"], cfg, xn, k_all, v_all, block_tables, start, chunk_len,
            block_size=block_size, layer=i, window=window, impl=impl,
            verify=True)
        x = x + h
        x = x + layers.mlp(lp["mlp"], cfg,
                           layers.apply_norm(lp["ln2"], cfg, x))
        return (x, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.apply_norm(params["ln_f"], cfg, x)          # all T positions
    logits = logits_fn(params, cfg, h)                     # (B, T, V)
    return logits, {"k": k, "v": v, "len": start}


def decode_step(params, cfg: ModelConfig, token, cache, impl=None):
    """token: (B,) int32.  One new token; cache['len'] counts tokens already
    in the cache (the new token is written at ring slot len % S).

    The full stacked cache rides in the scan CARRY and is updated with
    dynamic_update_index — XLA performs carry DUS in place, so a donated
    cache costs ONE buffer instead of the scan xs+ys double buffer (which
    blew the 16 GB/chip budget at decode_32k — EXPERIMENTS.md §Dry-run)."""
    B = token.shape[0]
    window = _window(cfg)
    new_len = cache["len"] + 1
    x = layers.embed(params["embed"], cfg, token).astype(cfg.compute_dtype)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        kc = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        out, kc, vc = block_decode(lp, cfg, x, kc, vc, new_len,
                                   window=window, impl=impl)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, i, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, i, 0)
        return (out, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.apply_norm(params["ln_f"], cfg, x[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "len": new_len}


def decode_step_paged(params, cfg: ModelConfig, token, cache, block_tables,
                      live, *, block_size, impl=None):
    """Paged-native fused decode: cache ``k``/``v`` are the arena's
    stacked PAGE POOLS (``kernels.paged_pool`` layout), ``len`` the
    per-slot (B,) lengths.  Each layer reads K/V in place at its own
    index of the stacked pools through ``block_tables`` and writes back
    only each live slot's ONE new row — the O(capacity x slot_tokens x
    layers) dense materialize/re-scatter round trip of the gather path
    never happens.  ``live`` masks dead/prefilling slots:
    their row writes route to the trash page and their lengths hold."""
    B = token.shape[0]
    window = _window(cfg)
    lens = jnp.asarray(cache["len"], jnp.int32)
    live = jnp.asarray(live, bool)
    x = layers.embed(params["embed"], cfg, token).astype(cfg.compute_dtype)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        x = constrain_activation(x)
        xn = layers.apply_norm(lp["ln1"], cfg, x[:, None])[:, 0]
        h, k_all, v_all = layers.attention_decode_paged(
            lp["attn"], cfg, xn, k_all, v_all, block_tables, lens, live,
            block_size=block_size, layer=i, window=window, impl=impl)
        x = x + h
        xn = layers.apply_norm(lp["ln2"], cfg, x[:, None])[:, 0]
        x = x + layers.mlp(lp["mlp"], cfg, xn)
        return (x, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.apply_norm(params["ln_f"], cfg, x[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "len": jnp.where(live, lens + 1, lens)}
