"""Mixture-of-Experts decoder (mixtral-8x7b, grok-1-314b families).

GShard/Switch-style capacity-based top-k routing: tokens are grouped per
sequence, the dispatch/combine tensors are (G, S, E, C) one-hots (cheap
relative to the expert GEMMs at these widths), and the expert FFN runs
through ``ops.grouped_matmul`` — the Pallas grouped-GEMM kernel on TPU.
The attention/backbone is shared with ``transformer``; only the FFN differs.

Aux load-balance loss (Switch, eq. 4) is returned so training can weight it.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from . import layers, transformer
from .config import ModelConfig
from .sharding import constrain_activation


# ---------------------------------------------------------------------------
# router + dispatch
# ---------------------------------------------------------------------------

def init_moe_mlp(key, cfg: ModelConfig):
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    dt = cfg.weight_dtype
    ks = jax.random.split(key, 4)
    return {
        "router": layers.dense_init(ks[0], (d, E), dt),
        "w_gate": layers.dense_init(ks[1], (E, d, f), dt),
        "w_up": layers.dense_init(ks[2], (E, d, f), dt),
        "w_down": layers.dense_init(ks[3], (E, f, d), dt),
    }


def _top_k_dispatch(router_probs, k: int, capacity: int):
    """router_probs: (G, S, E).  Returns combine (G, S, E, C) fp32, the
    aux load-balance loss and the number of token→expert assignments
    dropped by the capacity limit.  Capacity-dropped tokens get zero
    combine weight (residual passes them through)."""
    G, S, E = router_probs.shape
    combine = jnp.zeros((G, S, E, capacity), jnp.float32)
    probs = router_probs
    # fraction of tokens routed (first choice) per expert, for aux loss
    top1 = jnp.argmax(probs, axis=-1)
    me = jnp.mean(probs, axis=1)                         # (G, E)
    ce = jnp.mean(jax.nn.one_hot(top1, E), axis=1)        # (G, E)
    aux = jnp.mean(jnp.sum(me * ce, axis=-1)) * (E ** 2) / (E * 1.0)

    occupancy = jnp.zeros((G, E), jnp.int32)
    dropped = jnp.zeros((), jnp.float32)
    for _ in range(k):
        idx = jnp.argmax(probs, axis=-1)                  # (G, S)
        gate = jnp.take_along_axis(probs, idx[..., None], -1)[..., 0]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.int32)    # (G, S, E)
        pos = jnp.cumsum(mask, axis=1) - mask + occupancy[:, None]
        pos = jnp.sum(pos * mask, axis=-1)                # (G, S)
        keep = pos < capacity
        dropped = dropped + jnp.sum((~keep).astype(jnp.float32))
        onehot_c = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
        contrib = (gate * keep)[..., None, None] \
            * mask[..., None].astype(jnp.float32) * onehot_c[..., None, :]
        combine = combine + contrib
        occupancy = occupancy + jnp.sum(mask, axis=1)
        probs = probs * (1.0 - mask.astype(probs.dtype))  # mask out chosen
    # renormalize the kept gates so the k gates sum to 1 (mixtral semantics)
    denom = jnp.sum(combine, axis=(-2, -1), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    return combine, aux, dropped


# ---------------------------------------------------------------------------
# expert-capacity drop counter (ROADMAP PR 3 follow-up): chunked prefill
# changes the routing-group granularity, so outputs can diverge from
# one-shot prefill exactly when the capacity limit is BINDING — i.e. when
# tokens are dropped.  The counter makes that observable: the serving
# engine enables it for MoE services and reports per-step drop deltas in
# ``StepStats.moe_dropped_tokens``.  It is a process-global accumulator
# fed by ``jax.debug.callback`` (the only host-side channel out of a
# jitted step); the flag is checked at TRACE time, so training and other
# disabled paths pay nothing.  Per-step attribution is exact in the
# single-threaded serving loop (every step blocks on its sampled tokens,
# flushing the callbacks, before the next runtime steps) but only
# approximate if several MoE runtimes ever step concurrently; counts also
# include padding/garbage rows of masked serving batches — it is an
# observability signal, not an exact per-request audit.
# ---------------------------------------------------------------------------

class _MoeDropStats:
    __slots__ = ("dropped", "assigned")

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.dropped = 0.0
        self.assigned = 0.0

    @property
    def drop_rate(self) -> float:
        return self.dropped / self.assigned if self.assigned else 0.0


MOE_DROP_STATS = _MoeDropStats()
_DROP_COUNTER_ENABLED = False


def enable_drop_counter(on: bool = True) -> None:
    """Toggle drop accounting for traces built AFTER the call (already
    compiled functions keep their behaviour)."""
    global _DROP_COUNTER_ENABLED
    _DROP_COUNTER_ENABLED = bool(on)


def _note_drops(dropped, assigned) -> None:
    MOE_DROP_STATS.dropped += float(dropped)
    MOE_DROP_STATS.assigned += float(assigned)


MAX_ROUTING_GROUP = 2048


def moe_mlp(p, cfg: ModelConfig, x, *, impl=None):
    """x: (B, L, d) -> (B, L, d), plus aux loss.

    Long sequences are split into routing groups of <= MAX_ROUTING_GROUP
    tokens (GShard-style): expert capacity — and with it the (G, S, E, C)
    dispatch tensors — scales with the group, not the sequence (a 32k
    prefill would otherwise need C~10k and TB-scale one-hots)."""
    B, L, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    seg = min(L, MAX_ROUTING_GROUP)
    pad = (-L) % seg
    xg = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    G = xg.shape[1] // seg
    xg = xg.reshape(B * G, seg, d)
    capacity = max(1, int(cfg.moe_capacity_factor * k * seg / E))
    logits = layers.linear(xg.astype(jnp.float32),
                           p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)               # (BG, seg, E)
    combine, aux, dropped = _top_k_dispatch(probs, k, capacity)
    if _DROP_COUNTER_ENABLED:                             # trace-time gate
        jax.debug.callback(_note_drops, dropped,
                           jnp.asarray(float(k * B * G * seg), jnp.float32))
    dispatch = (combine > 0).astype(x.dtype)              # (BG, seg, E, C)
    # (BG, S, E, C) x (BG, S, d) -> (E, BG*C, d)
    expert_in = jnp.einsum("blec,bld->ebcd", dispatch, xg)
    expert_in = expert_in.reshape(E, B * G * capacity, d)
    gate = ops.grouped_matmul(expert_in, p["w_gate"], impl=impl)
    up = ops.grouped_matmul(expert_in, p["w_up"], impl=impl)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    out = ops.grouped_matmul(h, p["w_down"], impl=impl)
    out = out.reshape(E, B * G, capacity, d)
    y = jnp.einsum("blec,ebcd->bld", combine.astype(x.dtype), out)
    y = y.reshape(B, G * seg, d)
    return y[:, :L], aux


# ---------------------------------------------------------------------------
# blocks / model API (attention backbone shared with transformer)
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "ln1": layers.init_norm(ks[0], cfg),
        "attn": layers.init_attention(ks[1], cfg),
        "ln2": layers.init_norm(ks[2], cfg),
        "moe": init_moe_mlp(ks[3], cfg),
    }


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    return {
        "embed": layers.init_embedding(ks[0], cfg),
        "blocks": transformer.stack_layer_params(
            ks[1], cfg.num_layers, lambda k: init_block(k, cfg)),
        "ln_f": layers.init_norm(ks[2], cfg),
    }


def forward_hidden(params, cfg: ModelConfig, batch: Dict[str, Any], *,
                   train: bool = False, impl=None):
    tokens = batch["tokens"]
    B, L = tokens.shape
    h = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    positions = jnp.arange(L)[None]
    window = cfg.sliding_window

    def body(carry, lp):
        x, aux = carry
        x = constrain_activation(x)
        a, _ = layers.attention(lp["attn"], cfg,
                                layers.apply_norm(lp["ln1"], cfg, x),
                                positions=positions, window=window, impl=impl)
        x = x + a
        m, aux_l = moe_mlp(lp["moe"], cfg,
                           layers.apply_norm(lp["ln2"], cfg, x), impl=impl)
        return (x + m, aux + aux_l), None

    scan_body = jax.checkpoint(body) if train else body
    (h, aux), _ = jax.lax.scan(scan_body, (h, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    h = layers.apply_norm(params["ln_f"], cfg, h)
    return h, aux / cfg.num_layers


def logits_fn(params, cfg: ModelConfig, hidden):
    return layers.unembed(params["embed"], cfg, hidden)


init_cache = transformer.init_cache


def prefill(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            cache_size: Optional[int] = None, impl=None):
    tokens = batch["tokens"]
    B, L = tokens.shape
    window = cfg.sliding_window
    cache_size = cache_size or L
    if window is not None:
        cache_size = min(cache_size, window)
    else:
        cache_size = max(cache_size, L)  # full attention never trims
    h = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    positions = jnp.arange(L)[None]

    def body(carry, lp):
        carry = constrain_activation(carry)
        xn = layers.apply_norm(lp["ln1"], cfg, carry)
        a, (k, v) = layers.attention(lp["attn"], cfg, xn, positions=positions,
                                     window=window, impl=impl)
        x = carry + a
        m, _ = moe_mlp(lp["moe"], cfg,
                       layers.apply_norm(lp["ln2"], cfg, x), impl=impl)
        x = x + m
        if cache_size > L:
            pad = ((0, 0), (0, cache_size - L), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        elif cache_size < L:
            k, v = k[:, L - cache_size:], v[:, L - cache_size:]
            shift = L % cache_size
            k, v = jnp.roll(k, shift, axis=1), jnp.roll(v, shift, axis=1)
        return x, (k, v)

    h, (k, v) = jax.lax.scan(body, h, params["blocks"])
    h = layers.apply_norm(params["ln_f"], cfg, h[:, -1:])
    logits = logits_fn(params, cfg, h[:, 0])
    return logits, {"k": k, "v": v, "len": jnp.asarray(L, jnp.int32)}


def prefill_chunk(params, cfg: ModelConfig, batch, cache, *, chunk_len,
                  impl=None):
    """Chunked prefill (see ``transformer.prefill_chunk``).  The chunk is
    its own MoE routing group: expert capacity scales with the bucket, not
    the prompt, so per-token outputs match one-shot prefill exactly
    whenever capacity is not binding (padding rows past ``chunk_len`` do
    compete for capacity at tight ``moe_capacity_factor``)."""
    tokens = batch["tokens"]
    window = cfg.sliding_window
    x = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    start = cache["len"]

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        x = constrain_activation(x)
        kc = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        xn = layers.apply_norm(lp["ln1"], cfg, x)
        a, kc, vc = layers.attention_chunk(lp["attn"], cfg, xn, kc, vc,
                                           start, chunk_len, window=window,
                                           impl=impl)
        x = x + a
        m, _ = moe_mlp(lp["moe"], cfg,
                       layers.apply_norm(lp["ln2"], cfg, x), impl=impl)
        x = x + m
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
    """Paged-native chunked prefill (see ``transformer.prefill_chunk_paged``
    and ``prefill_chunk``'s routing-group caveat): chunk K/V rows scatter
    straight into the arena page pools, the MoE FFN is unchanged."""
    tokens = batch["tokens"]
    window = cfg.sliding_window
    x = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    start = jnp.asarray(cache["len"], jnp.int32).reshape(-1)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        x = constrain_activation(x)
        xn = layers.apply_norm(lp["ln1"], cfg, x)
        a, k_all, v_all = layers.attention_chunk_paged(
            lp["attn"], cfg, xn, k_all, v_all, block_tables, start, chunk_len,
            block_size=block_size, layer=i, window=window, impl=impl)
        x = x + a
        m, _ = moe_mlp(lp["moe"], cfg,
                       layers.apply_norm(lp["ln2"], cfg, x), impl=impl)
        x = x + m
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
    """Speculative-decoding verify (see ``transformer.verify_step_paged``):
    the ``prefill_chunk_paged`` body with the head over ALL T positions
    instead of ``take_chunk_last`` — logits come back ``(B, T, V)`` and
    ``cache['len']`` is returned unchanged (the engine commits lengths
    after acceptance).  Expert routing stays per-chunk, matching the
    chunked-prefill granularity the drafts were verified against."""
    tokens = batch["tokens"]
    window = cfg.sliding_window
    x = layers.embed(params["embed"], cfg, tokens).astype(cfg.compute_dtype)
    start = jnp.asarray(cache["len"], jnp.int32).reshape(-1)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        x = constrain_activation(x)
        xn = layers.apply_norm(lp["ln1"], cfg, x)
        a, k_all, v_all = layers.attention_chunk_paged(
            lp["attn"], cfg, xn, k_all, v_all, block_tables, start, chunk_len,
            block_size=block_size, layer=i, window=window, impl=impl,
            verify=True)
        x = x + a
        m, _ = moe_mlp(lp["moe"], cfg,
                       layers.apply_norm(lp["ln2"], cfg, x), impl=impl)
        x = x + m
        return (x, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.apply_norm(params["ln_f"], cfg, x)          # all T positions
    logits = logits_fn(params, cfg, h)                     # (B, T, V)
    return logits, {"k": k, "v": v, "len": start}


def _moe_mlp_single(p, cfg: ModelConfig, x_t, *, impl=None):
    """Decode-time MoE for a (B, d) token batch.

    Routes each slot's token as its OWN dispatch group (B groups of S=1)
    through the same capacity machinery as prefill — never gathers expert
    weights per token (that would stream B*k full expert FFNs from HBM),
    and the grouped matmuls still see one fused (E, B*C, d) stack.
    Per-slot grouping matters for the serving engine: a shared group would
    make tokens compete for expert capacity across requests, so a slot's
    output would depend on its batch neighbours (and, under the paged
    arena's fixed-capacity batch, on unoccupied slots' garbage rows) —
    per-token groups keep every decode row numerically independent."""
    y, _ = moe_mlp(p, cfg, x_t[:, None], impl=impl)
    return y[:, 0]


def decode_step(params, cfg: ModelConfig, token, cache, impl=None):
    B = token.shape[0]
    window = cfg.sliding_window
    new_len = cache["len"] + 1
    x = layers.embed(params["embed"], cfg, token).astype(cfg.compute_dtype)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        x = constrain_activation(x)
        kc = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        S = kc.shape[1]
        eff_window = None if (window is None or S <= window) else window
        xn = layers.apply_norm(lp["ln1"], cfg, x[:, None])[:, 0]
        a, kc, vc = layers.attention_decode(lp["attn"], cfg, xn, kc, vc,
                                            new_len, window=eff_window,
                                            impl=impl)
        x = x + a
        xn = layers.apply_norm(lp["ln2"], cfg, x[:, None])[:, 0]
        x = x + _moe_mlp_single(lp["moe"], cfg, xn, impl=impl)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, i, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, i, 0)
        return (x, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.apply_norm(params["ln_f"], cfg, x[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "len": new_len}


def decode_step_paged(params, cfg: ModelConfig, token, cache, block_tables,
                      live, *, block_size, impl=None):
    """Paged-native fused decode (see ``transformer.decode_step_paged``):
    attention streams K/V through the block table, the per-token-group
    MoE FFN keeps every decode row numerically independent of its batch
    neighbours (so fixed-capacity garbage rows stay harmless)."""
    window = cfg.sliding_window
    lens = jnp.asarray(cache["len"], jnp.int32)
    live = jnp.asarray(live, bool)
    x = layers.embed(params["embed"], cfg, token).astype(cfg.compute_dtype)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, i = xs
        x = constrain_activation(x)
        xn = layers.apply_norm(lp["ln1"], cfg, x[:, None])[:, 0]
        a, k_all, v_all = layers.attention_decode_paged(
            lp["attn"], cfg, xn, k_all, v_all, block_tables, lens, live,
            block_size=block_size, layer=i, window=window, impl=impl)
        x = x + a
        xn = layers.apply_norm(lp["ln2"], cfg, x[:, None])[:, 0]
        x = x + _moe_mlp_single(lp["moe"], cfg, xn, impl=impl)
        return (x, k_all, v_all), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    h = layers.apply_norm(params["ln_f"], cfg, x[:, None])[:, 0]
    logits = logits_fn(params, cfg, h)
    return logits, {"k": k, "v": v, "len": jnp.where(live, lens + 1, lens)}
