"""Token samplers for the serving engine.

Per-slot counter-based PRNG streams
-----------------------------------

A slot's sample stream must be a *pure function of the request*, never of
batch composition: the engine decodes at full static capacity, slots are
admitted/evicted/parked in arbitrary order, and a batch-wide
``jax.random.split`` would make every sampled token depend on which other
slots happen to be live.  Instead each draw derives its key by folding a
counter chain into one base key:

    key = fold_in(fold_in(fold_in(fold_in(base, seed), sample_idx),
                          stream), offset)

* ``seed`` — the request's seed (defaults to its rid);
* ``sample_idx`` — which of the request's n parallel samples this row is;
* ``stream`` — which consumer is drawing (``STREAM_DECODE`` for the
  ordinary one-token-per-step path, ``STREAM_DRAFT`` for draft-model
  proposals, ``STREAM_VERIFY`` / ``STREAM_CORRECTION`` for speculative
  rejection sampling) so speculation never perturbs the decode stream;
* ``offset`` — the emitted length at which the draw happens, i.e. a
  per-request monotonic counter.

Greedy sampling (``temperature <= 0``) never touches a key at all, which is
what makes park/resume, speculative on/off, and batch-composition changes
bit-identical for greedy services by construction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

# Stream tags (the third fold_in in the counter chain).
STREAM_DECODE = 0      # the ordinary decode-loop sample
STREAM_DRAFT = 1       # draft-model proposals (speculative decoding)
STREAM_VERIFY = 2      # accept/reject uniforms in speculative_verify
STREAM_CORRECTION = 3  # residual/bonus draw in speculative_verify


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    temperature: float = 0.0     # 0 => greedy
    top_k: int = 0               # 0 => disabled


def slot_keys(base_key, seeds, sample_ids, offsets, stream: int = STREAM_DECODE):
    """Per-row keys from the counter chain: (B,) int arrays -> (B,) keys."""
    seeds = jnp.asarray(seeds, jnp.uint32)
    sample_ids = jnp.asarray(sample_ids, jnp.uint32)
    offsets = jnp.asarray(offsets, jnp.uint32)

    def one(seed, sidx, off):
        k = jax.random.fold_in(base_key, seed)
        k = jax.random.fold_in(k, sidx)
        k = jax.random.fold_in(k, jnp.uint32(stream))
        return jax.random.fold_in(k, off)

    return jax.vmap(one)(seeds, sample_ids, offsets)


def _filtered(logits, cfg: SamplerConfig):
    """Temperature-scaled, top_k-filtered logits (f32). temperature > 0."""
    scaled = logits.astype(jnp.float32) / cfg.temperature
    if cfg.top_k > 0:
        top_vals, _ = jax.lax.top_k(scaled, cfg.top_k)
        cutoff = top_vals[..., -1:]
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    return scaled


def _apply_mask(out, live, occupancy, fill_token):
    mask = None
    if live is not None:
        mask = jnp.asarray(live)
    if occupancy is not None:
        occ = jnp.asarray(occupancy)
        mask = occ if mask is None else jnp.logical_and(mask, occ)
    if mask is not None:
        out = jnp.where(mask, out, jnp.asarray(fill_token, jnp.int32))
    return out, mask


def sample(logits, key, cfg: SamplerConfig = SamplerConfig(), *,
           live=None, occupancy=None, fill_token: int = 0):
    """logits: (B, V) -> (B,) int32 — single shared key (sync/batch path).

    Two optional (B,) bool masks keep the fused batch-wide sample
    shape-stable and deterministic regardless of which rows are real:

    * ``occupancy`` — the paged arena decodes at full static capacity, so
      rows of unoccupied slots carry garbage logits and must never emit;
    * ``live`` — slots that already finished (EOS / their own
      ``max_new_tokens``) but still hold a slot until the next evict pass.

    Rows masked by either are overwritten with ``fill_token``.

    The continuous engine never uses this for stochastic sampling — it
    routes through :func:`sample_per_slot` so each row's stream is
    batch-composition independent.
    """
    if cfg.temperature <= 0.0:
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        out = jax.random.categorical(key, _filtered(logits, cfg),
                                     axis=-1).astype(jnp.int32)
    out, _ = _apply_mask(out, live, occupancy, fill_token)
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "stream", "fill_token"))
def sample_per_slot(logits, base_key, seeds, sample_ids, offsets,
                    cfg: SamplerConfig = SamplerConfig(), *,
                    stream: int = STREAM_DECODE,
                    live=None, occupancy=None, fill_token: int = 0):
    """logits: (B, V) -> (B,) int32 with per-row counter-based keys.

    Row ``i`` draws with ``slot_keys(base, seeds[i], sample_ids[i],
    offsets[i], stream)`` — a pure function of that request's identity and
    progress, so its token stream is bit-identical whether it runs alone,
    in a full batch, or across a park/resume cycle.  Greedy never touches
    a key.  One compiled program per call shape (``jit_sample_per_slot``
    in a profile), not one dispatch per operation.
    """
    if cfg.temperature <= 0.0:
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        keys = slot_keys(base_key, seeds, sample_ids, offsets, stream)
        scaled = _filtered(logits, cfg)
        out = jax.vmap(
            lambda k, row: jax.random.categorical(k, row)
        )(keys, scaled).astype(jnp.int32)
    out, _ = _apply_mask(out, live, occupancy, fill_token)
    return out


def _masked_probs(logits, cfg: SamplerConfig):
    """Softmax under the SAME temperature/top_k filter sampling uses."""
    return jax.nn.softmax(_filtered(logits, cfg), axis=-1)


def speculative_verify(target_logits, draft_logits, draft_tokens,
                       base_key, seeds, sample_ids, offsets,
                       cfg: SamplerConfig = SamplerConfig(), *,
                       live=None, occupancy=None, fill_token: int = 0):
    """Accept/reject k draft tokens against one fused target launch.

    Shapes (T = k+1 verified positions):

    * ``target_logits`` — (B, T, V): the target model's logits after each
      of the T fed tokens ``[last_emitted, d_1 .. d_k]``; row ``j`` is the
      target distribution for the position draft token ``d_{j+1}``
      occupies, and row ``k`` is the bonus position.
    * ``draft_logits`` — (B, k, V): the draft distributions ``d_{j+1}``
      was sampled from (ignored under greedy).
    * ``draft_tokens`` — (B, k) int32: the proposals ``d_1 .. d_k``.
    * ``offsets`` — (B,): emitted length at the round's first verified
      position (the per-request stream counter).

    Returns ``(tokens, n_emit)`` — ``tokens`` (B, T) int32 holding the
    emitted tokens left-aligned (accepted drafts then the
    correction/bonus; tail is ``fill_token``), ``n_emit`` (B,) int32 in
    ``[0, T]`` (0 only for masked rows).

    Greedy (``temperature <= 0``) accepts the longest prefix where
    ``d_{j+1} == argmax(target[j])`` and emits argmaxes — bit-identical
    to the non-speculative oracle by construction, key-free.  Stochastic
    uses exact leave-one-out rejection sampling (accept ``d`` w.p.
    ``min(1, p(d)/q(d))``; on first reject draw from
    ``normalize(max(p-q, 0))``; on all-accept draw the bonus from the
    target), so emitted tokens are distributed exactly as sampling the
    target one token at a time.
    """
    B, T, V = target_logits.shape
    k = T - 1
    draft_tokens = draft_tokens.astype(jnp.int32)

    if cfg.temperature <= 0.0:
        targets = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)  # (B,T)
        match = draft_tokens == targets[:, :k]                          # (B,k)
        prefix = jnp.cumprod(match.astype(jnp.int32), axis=-1)
        n_acc = prefix.sum(axis=-1)                                     # (B,)
        out = targets
    else:
        p = _masked_probs(target_logits, cfg)                 # (B,T,V)
        q = _masked_probs(draft_logits, cfg)                  # (B,k,V)
        rows = jnp.arange(B)[:, None]
        cols = jnp.arange(k)[None, :]
        p_d = p[rows, cols, draft_tokens]                     # (B,k)
        q_d = q[rows, cols, draft_tokens]
        vkeys = slot_keys(base_key, seeds, sample_ids, offsets,
                          STREAM_VERIFY)
        u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(vkeys)
        accept = u * q_d <= p_d                               # (B,k)
        prefix = jnp.cumprod(accept.astype(jnp.int32), axis=-1)
        n_acc = prefix.sum(axis=-1)                           # (B,) in [0,k]
        # Residual distribution at the first rejected position; at the
        # bonus position (n_acc == k) the draft proposed nothing, so the
        # residual degenerates to the target itself (q := 0 there).
        q_pad = jnp.concatenate([q, jnp.zeros((B, 1, V), q.dtype)], axis=1)
        p_at = p[jnp.arange(B), n_acc]                        # (B,V)
        q_at = q_pad[jnp.arange(B), n_acc]
        resid = jnp.maximum(p_at - q_at, 0.0)
        rsum = resid.sum(axis=-1, keepdims=True)
        resid = jnp.where(rsum > 0, resid / jnp.maximum(rsum, 1e-30), p_at)
        ckeys = slot_keys(base_key, seeds, sample_ids, offsets,
                          STREAM_CORRECTION)
        corr = jax.vmap(
            lambda kk, pr: jax.random.categorical(kk, jnp.log(pr + 1e-30))
        )(ckeys, resid).astype(jnp.int32)
        pos = jnp.arange(T)[None, :]
        out = jnp.where(pos < n_acc[:, None], draft_tokens_padded(draft_tokens),
                        jnp.where(pos == n_acc[:, None], corr[:, None],
                                  jnp.asarray(fill_token, jnp.int32)))

    n_emit = n_acc + 1
    masked, mask = _apply_mask(jnp.ones((B,), jnp.int32), live, occupancy, 0)
    if mask is not None:
        n_emit = jnp.where(mask, n_emit, 0)
        out = jnp.where(mask[:, None], out, jnp.asarray(fill_token, jnp.int32))
    # Zero the tail past n_emit so garbage positions can't leak.
    pos = jnp.arange(T)[None, :]
    out = jnp.where(pos < n_emit[:, None], out,
                    jnp.asarray(fill_token, jnp.int32))
    return out.astype(jnp.int32), n_emit.astype(jnp.int32)


def draft_tokens_padded(draft_tokens):
    """(B, k) -> (B, k+1): pad one bogus column so draft/correction selects
    share a (B, T) shape (the pad is never selected — position ``k`` can
    only be the bonus draw)."""
    B = draft_tokens.shape[0]
    return jnp.concatenate(
        [draft_tokens, jnp.zeros((B, 1), draft_tokens.dtype)], axis=1)
