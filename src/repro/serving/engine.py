"""Live serving engine: continuous-batching decode over persistent slots,
driven by an EPARA ParallelPlan.

``ServiceRuntime`` owns one service's params and its DP replica groups.
The default ``mode="continuous"`` keeps a persistent in-flight batch of
decode slots per group; each ``step()``:

  (a) **evicts** slots whose request hit EOS or its own ``max_new_tokens``,
  (b) **admits** queued requests from the BS/MF composer into the freed
      slots (``compose(limit=free)``),
  (b2) advances **chunked prefill**: in-progress prompts are split into
      fixed bucket-sized chunks written straight through the arena's block
      tables, at most ``prefill_chunk`` tokens per group per step — so a
      long prompt never stalls live decode slots for more than one chunk
      (head-of-line isolation), and prefill compiles once per chunk
      BUCKET instead of once per prompt length,

with a **radix prefix cache** (``serving/prefix_cache.py``) in front of
(b): admissions of prefix-cacheable families look up the longest cached
prompt prefix and stitch its blocks into the new slot's table
(``arena.alloc(shared=...)``) so (b2) starts after the hit boundary;
divergence inside a shared block copies-on-write, retention follows the
task category (``ParallelPlan.prefix_cache``), and hit/COW/eviction
counts land in ``StepStats``,
  (c) runs **one fused decode step** for every decoding slot, with
      per-slot ``len`` vectors (the decode kernels mask per-batch
      ``cache_len``) and sampling masked by occupancy.

Two cache data planes back the slot loop (``kvcache_impl``):

* ``"paged"`` (default) — a fixed-capacity ``KVArena`` per group, sized
  from the plan (``plan.max_in_flight`` slots x paged token blocks,
  clipped to what the device's memory holds).
  Admission scatters only the new request's pages into the arena
  (``arena.alloc`` + ``arena.write_prefill``), eviction is a free-list
  operation, and decode always runs at the full static ``(capacity, ...)``
  shape with an occupancy mask — so the fused step compiles EXACTLY ONCE
  per service no matter how the live batch size churns, and no admission
  ever copies the live batch.  For attention families the paged layout is
  NATIVE to the hot loop (``ModelApi.decode_step_paged`` /
  ``prefill_chunk_paged``): attention streams K/V in place through the
  block tables (``ops.paged_decode_attention`` /
  ``paged_chunk_attention`` — scalar-prefetch Pallas on TPU, per-slot
  up-to-len gather on CPU) and writes back only each live slot's NEW
  rows, so the old ``dense_view`` materialize / ``append_rows``
  re-scatter round trip — O(capacity x slot_tokens x layers) HBM traffic
  per emitted token — never happens.  Pure-SSM families keep the
  (already gather-free) per-slot state side-channel, and ring
  (sliding-window) layouts keep the dense-view fallback, which also
  survives as the test oracle (``paged_native=False``).
* ``"dense"`` — the pre-arena pytree path (``kvcache.select_slots`` /
  ``merge``), temporarily retained for comparison: every admission
  re-materializes the whole live cache and every live-batch-size change
  retraces the decode step.  ``benchmarks/continuous_batching.py`` reports
  both implementations' retrace counts and admission-copy bytes.

Two further **decoding modes** ride on the paged arena, gated by the
plan's task category (``ParallelPlan.speculate`` / ``n_samples``):

* **speculative** (latency services) — a small same-family draft model
  shadows each slot in its own ``KVArena``; once the draft cache catches
  up (chunked, off the decode path) each round runs k+1 fused draft
  steps and ONE fused target verify launch (``api.verify_step_paged``
  through the existing chunk-attention kernels), committing 1..k+1
  tokens.  Greedy acceptance is bit-identical to plain decode.
* **n>1 parallel sampling** (frequency services) — sibling slots fork
  off a finished prefill sharing the prompt's blocks by refcount, pay
  zero prefill compute, and diverge through copy-on-write.

Both are built on per-slot COUNTER-BASED sampling streams
(``serving/sampler.py``): each drawn token is a pure function of
(request seed, sample index, stream, emitted offset) — never of batch
composition, step count, or park/resume history.

``step()`` returns a ``StepStats`` telemetry record (results + queue-time
estimate + copy/retrace counters); the launcher feeds
``StepStats.queue_time_s`` back into the control plane's handler state
(``EdgeCloudControlPlane.set_queue_time``) so offload decisions see real
data-plane backpressure.  The pre-slot run-to-completion path is preserved
behind ``mode="sync"``; all paths produce identical greedy tokens.

Request-level DP round-robins admissions across groups (sticky for
stateful archs); sticky session pins are released through the engine's
eviction hook once a session has no queued or in-flight requests left.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allocator import DPGroupRouter, ParallelPlan
from repro.core.categories import Outcome
from repro.models.config import ModelConfig
from repro.models.registry import ModelApi, model_api
from repro.obs.trace import NULL_TRACER

from . import kvcache
from .admission import AdmissionController, AdmissionReject, ParkedEntry
from .arena import KVArena
from .batching import ComposedBatch, QueuedItem, make_composer
from .prefix_cache import PrefixHit, RadixPrefixCache
from .sampler import (STREAM_DECODE, STREAM_DRAFT, SamplerConfig,
                      sample_per_slot, speculative_verify)

DEFAULT_MAX_SEQ_LEN = 256
DEFAULT_BLOCK_SIZE = 32

# Families whose paged KV content is a pure function of the prompt token
# ids — the prerequisite for cross-request block sharing.  SSM/hybrid carry
# per-slot recurrent state a shared prefix cannot reconstruct, and
# enc-dec/VLM cache content depends on non-token inputs (audio embeddings,
# image prefixes), so sharing by token hash would alias distinct requests.
PREFIX_CACHEABLE_FAMILIES = ("dense", "moe")


@dataclasses.dataclass
class GenerationRequest:
    rid: int
    tokens: np.ndarray               # prompt (L,) int32
    max_new_tokens: int = 16
    stream: int = 0
    extras: Optional[Dict[str, Any]] = None   # e.g. image/frame embeddings
    submitted_s: float = 0.0
    eos_token: Optional[int] = None  # evict the slot early on this token
    deadline_s: float = 0.0          # absolute deadline in the caller's
    #                                  clock (0 = none); the admission
    #                                  controller's slack/verdict input
    seed: Optional[int] = None       # sampling stream seed (None -> rid):
    #                                  every token this request draws is a
    #                                  pure function of (seed, sample_idx,
    #                                  emitted offset), never of the batch
    n_samples: int = 1               # n-way parallel sampling: n-1 forks
    #                                  share the prompt's blocks and
    #                                  diverge by copy-on-write (capped by
    #                                  the plan's resolved_n_samples())


@dataclasses.dataclass
class GenerationResult:
    rid: int
    tokens: np.ndarray               # generated ids (n,)
    prefill_s: float                 # this request's own prefill wall time
    decode_s: float                  # admit→finish wall time (continuous)
    group: int
    admitted_s: float = 0.0          # logical clock at admission
    finished_s: float = 0.0          # logical clock at eviction
    decode_steps: int = 0            # fused steps this request took part in
    sample: int = 0                  # which of the request's n parallel
    #                                  samples this result is (0 = primary)


@dataclasses.dataclass
class StepStats:
    """One scheduling round's telemetry.  ``results`` carries the finished
    requests (what ``drain`` accumulates); the rest is the feedback the
    control plane's handler consumes (queue-time backpressure) and the
    data-plane efficiency counters the benchmarks report."""
    results: List[GenerationResult]
    now: float = 0.0
    admitted: int = 0                # requests admitted this step
    evicted: int = 0                 # slots released this step
    in_flight: int = 0               # occupied slots after the step
    pending: int = 0                 # queued requests after the step
    queue_time_s: float = 0.0        # est. wait for a new arrival (handler)
    admission_copy_bytes: int = 0    # cache bytes COPIED by slot churn this
    #                                  step (admission merges, COW copies +
    #                                  the dense impl's eviction compaction)
    chunk_write_bytes: int = 0       # cache bytes WRITTEN by chunked
    #                                  prefill this step — appends of fresh
    #                                  rows, not copies of existing cache
    #                                  (split from admission_copy_bytes so
    #                                  the zero-copy admission assertion
    #                                  measures what it claims)
    whole_cache_copies: int = 0      # live-batch copies this step (dense
    #                                  merge or select_slots compaction)
    decode_steps: int = 0            # fused decode invocations this step
    prefill_chunk_tokens: int = 0    # prompt tokens prefilled this step by
    #                                  the piggybacked chunk phase
    oneshot_prefills: int = 0        # admissions that took the one-shot
    #                                  prefill path this step (ring/sliding-
    #                                  window layouts and chunking-disabled
    #                                  configs — the documented fallback,
    #                                  now observable instead of silent)
    prefix_lookups: int = 0          # prefix-cache lookups this step
    prefix_hits: int = 0             # admissions that reused cached blocks
    prefix_hit_tokens: int = 0       # prompt tokens served from the cache
    prefix_evicted_blocks: int = 0   # cached blocks reclaimed (LRU) this step
    prefix_cow_blocks: int = 0       # copy-on-write block copies this step
    moe_dropped_tokens: float = 0.0  # MoE expert-capacity drops this step
    #                                  (token-assignments past capacity;
    #                                  nonzero under binding capacity, where
    #                                  chunked prefill may diverge)
    # -- admission-control telemetry (serving/admission.py) -------------
    rejected: List[AdmissionReject] = dataclasses.field(
        default_factory=list)        # requests shed this step, each with
    #                                  an explicit verdict — the launcher
    #                                  routes OFFLOAD verdicts through the
    #                                  handler instead of dropping them
    deadline_missed: int = 0         # DEADLINE_MISSED verdicts this step
    congestion_rejects: int = 0      # CONGESTION verdicts this step
    offload_verdicts: int = 0        # OFFLOAD verdicts this step
    failed_rejects: int = 0          # FAILED verdicts this step (fault-
    #                                  tolerance terminal verdict: lost to
    #                                  a crash/drop and out of retries)
    evacuated: int = 0               # requests stripped out by crash
    #                                  evacuation since the last step
    #                                  (returned to the supervisor for
    #                                  resubmission on survivors)
    preempted: int = 0               # live slots parked this step
    resumed: int = 0                 # parked requests re-admitted this step
    parked: int = 0                  # parked requests outstanding after
    #                                  the step (KV frozen in the arena)
    # -- speculative / parallel decoding telemetry ----------------------
    draft_steps: int = 0             # fused DRAFT decode steps this step
    verify_launches: int = 0         # fused verify launches this step
    accepted_tokens: int = 0         # target tokens committed by verify
    #                                  (acceptance rate = accepted_tokens
    #                                  / verify_launches / (k+1))
    spec_slots: int = 0              # live slots speculating after the step
    forks_spawned: int = 0           # n>1 sibling slots forked this step
    fork_shortfall: int = 0          # requested forks not spawned (slot or
    #                                  block pressure; primary still runs)
    spec_degraded: int = 0           # slots that fell back to plain decode
    #                                  this step (draft alloc failure or
    #                                  park/resume)


class _Slot:
    """One in-flight request occupying a decode slot.  Under the paged
    arena, ``slot_id`` is the request's arena slot handle (its row in the
    block table); under the dense impl it is the position in the group's
    compacted cache batch axis.

    A slot admitted through the chunked-prefill path starts with
    ``first_token=None``: it holds its arena slot while ``consumed``
    prompt tokens are written chunk by chunk, and flips into decoding via
    ``begin_decode`` when the final chunk's logits yield the first token.
    """
    __slots__ = ("req", "emitted", "done", "prefill_s", "admit_wall",
                 "submit_wall", "decode_start_wall", "finish_wall",
                 "admitted_s", "steps", "slot_id", "prefilling", "consumed",
                 "sample_idx", "spec", "draft_len")

    def __init__(self, req: GenerationRequest, first_token: Optional[int],
                 prefill_s: float, admit_wall: float, admitted_s: float,
                 slot_id: int = -1,
                 decode_start_wall: Optional[float] = None,
                 submit_wall: Optional[float] = None):
        self.req = req
        self.prefill_s = prefill_s
        self.admit_wall = admit_wall
        # when the request reached this runtime (its first token's wait
        # counts from here); admission time where nothing recorded it
        self.submit_wall = admit_wall if submit_wall is None else submit_wall
        self.finish_wall = 0.0
        self.admitted_s = admitted_s
        self.steps = 0
        self.slot_id = slot_id
        self.consumed = 0                   # prompt tokens prefilled so far
        #                                     (a prefix hit starts past 0)
        self.sample_idx = 0                 # 0 = primary; >0 = n>1 fork
        self.spec = False                   # draft slot allocated + chasing
        self.draft_len = 0                  # draft-cache rows written so far
        if first_token is None:             # chunked prefill in progress
            self.prefilling = True
            self.emitted: List[int] = []
            self.done = False
            self.decode_start_wall = admit_wall
        else:
            self.begin_decode(first_token,
                              admit_wall + prefill_s
                              if decode_start_wall is None
                              else decode_start_wall)

    def begin_decode(self, first_token: int, wall: float) -> None:
        """First token sampled: prefill COMPLETED at ``wall``.  Decode
        timing starts here — under chunking that is several steps after
        admission, so ``GenerationResult.decode_s`` stays truthful instead
        of silently absorbing the chunked prefill's wall time."""
        self.prefilling = False
        self.emitted = [first_token]
        self.decode_start_wall = wall
        self.done = (len(self.emitted) >= self.req.max_new_tokens
                     or (self.req.eos_token is not None
                         and first_token == self.req.eos_token))
        if self.done:
            self.finish_wall = wall

    def push(self, token: int) -> None:
        self.emitted.append(token)
        if (len(self.emitted) >= self.req.max_new_tokens
                or (self.req.eos_token is not None
                    and token == self.req.eos_token)):
            self.done = True
            self.finish_wall = time.perf_counter()


class _GroupState:
    """Persistent in-flight state of one DP replica group: the slot
    handles plus either a ``KVArena`` (paged) or a compacted cache pytree
    (dense)."""
    __slots__ = ("cache", "slots", "arena", "prefix", "draft")

    def __init__(self):
        self.cache = None            # dense impl only
        self.arena: Optional[KVArena] = None
        self.prefix: Optional[RadixPrefixCache] = None
        self.draft: Optional[KVArena] = None   # draft model's shadow arena
        self.slots: List[_Slot] = []

    @property
    def live(self) -> int:
        return len(self.slots)


class _MeshStep:
    """A jitted step that traces, compiles and runs under its mesh."""

    def __init__(self, jitted, mesh):
        self.jitted, self.mesh = jitted, mesh

    def __call__(self, *args):
        with jax.set_mesh(self.mesh):
            return self.jitted(*args)

    def lower(self, *args):
        with jax.set_mesh(self.mesh):
            return self.jitted.lower(*args)


class ServiceRuntime:
    """One deployed service: params + plan + DP groups of decode slots."""

    def __init__(self, cfg: ModelConfig, params, plan: ParallelPlan, *,
                 prefill_fn: Optional[Callable] = None,
                 decode_fn: Optional[Callable] = None,
                 sampler: SamplerConfig = SamplerConfig(), seed: int = 0,
                 impl: Optional[str] = None, mode: str = "continuous",
                 kvcache_impl: str = "paged",
                 max_seq_len: int = DEFAULT_MAX_SEQ_LEN,
                 block_size: int = DEFAULT_BLOCK_SIZE,
                 pool_blocks: Optional[int] = None,
                 chunked_prefill: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: Optional[Any] = None,
                 paged_native: Optional[bool] = None,
                 mesh=None,
                 on_evict: Optional[Callable] = None,
                 admission_policy: Optional[str] = None,
                 preempt: bool = True,
                 draft_params=None, draft_cfg: Optional[ModelConfig] = None,
                 speculate: Optional[int] = None,
                 tracer=None, metrics=None,
                 obs_name: Optional[str] = None):
        if mode not in ("continuous", "sync"):
            raise ValueError(f"mode must be continuous|sync, got {mode!r}")
        if kvcache_impl not in ("paged", "dense"):
            raise ValueError(
                f"kvcache_impl must be paged|dense, got {kvcache_impl!r}")
        # paged-KV precision: the arena quantizes page pools to int8 when
        # the plan says so (explicitly or via its task category).  Dense
        # caches are never quantized — an EXPLICIT int8 ask on a dense
        # engine is a config error; the category-derived default silently
        # keeps native precision (there are no page pools to quantize).
        if getattr(plan, "kv_dtype", -1) == "int8" and kvcache_impl != "paged":
            raise ValueError(
                "kv_dtype='int8' requires kvcache_impl='paged' (only page "
                "pools are block-quantized); dense caches keep the model's "
                "native dtype")
        self.kv_dtype = (plan.resolved_kv_dtype()
                         if kvcache_impl == "paged" else "bf16")
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.mode = mode
        self.kvcache_impl = kvcache_impl
        self.max_seq_len = max_seq_len
        self.block_size = block_size
        self.pool_blocks = pool_blocks
        self.on_evict = on_evict
        # -- observability (repro/obs): default-off and byte-inert --------
        # the NULL_TRACER's ``enabled = False`` lets every call site skip
        # building args entirely; neither layer ever touches a jax value,
        # so enabling them cannot change tokens or compile counts
        self.trace = NULL_TRACER if tracer is None else tracer
        self.metrics = metrics
        self._obs_named = obs_name is not None
        self.obs_name = obs_name if obs_name is not None else cfg.name
        self.prefill_seconds = 0.0   # cumulative per-request prefill wall
        #                              time (calibration's prefill_token_s
        #                              numerator)
        self._submit_wall: Dict[int, float] = {}  # rid -> submit wall time
        self._queue_wait: Dict[int, float] = {}   # rid -> measured wait
        self.api: ModelApi = model_api(cfg)
        self.router = DPGroupRouter(plan)
        self.composer = make_composer(plan)
        self.sampler = sampler
        self._key = jax.random.PRNGKey(seed)
        self.groups: Dict[int, _GroupState] = {
            g: _GroupState() for g in range(max(1, plan.dp))}
        # deadline-aware admission control: policy from the plan's knob
        # unless overridden; "fifo" (the default) keeps the controller
        # inert — identical legacy behavior, no shedding, no preemption
        self.admission = AdmissionController(self, admission_policy,
                                             preempt=preempt)
        if self.admission.active and mode != "continuous":
            raise ValueError(
                "admission policy 'sdf' requires mode='continuous' (slack "
                "ordering and preemption act on the slot loop)")
        self.decode_steps = 0        # fused decode invocations (all groups)
        self.decode_traces = 0       # XLA (re)compilations of the fused step
        self.prefill_traces = 0
        self.admission_copy_bytes = 0
        self.chunk_write_bytes = 0   # fresh rows appended by chunked prefill
        self.whole_cache_copies = 0  # admissions that copied the live batch
        self.prefill_chunk_calls = 0  # chunk invocations (all groups)
        self.prefill_tokens_computed = 0  # prompt tokens actually run
        #                                   through prefill compute (cache
        #                                   hits skip theirs)
        self.oneshot_prefills = 0    # admissions via one-shot prefill
        self._session_refs: Dict[int, int] = {}
        self._service_ewma_s = 0.0   # EWMA of per-request service time
        self._prefix_hit_ewma = 0.0  # EWMA of cached-prompt-token fraction
        self._paged_decode_fn = None
        self._chunk_fns: Dict[Any, Callable] = {}
        self._moe_stats = None
        if cfg.family == "moe":
            # expert-capacity drop observability: chunked prefill changes
            # the routing-group granularity, so divergence under binding
            # capacity shows up as a nonzero drop counter (global per
            # process; documented in models/moe.py)
            from repro.models import moe as _moe
            _moe.enable_drop_counter(True)
            self._moe_stats = _moe.MOE_DROP_STATS

        # -- chunked (piggybacked) prefill configuration ------------------
        # ring (sliding-window) cache layouts wrap positions mod the
        # window, which the linear chunk writes do not model — those
        # configs keep the one-shot admission prefill
        ring = (cfg.sliding_window is not None
                and cfg.sliding_window < self.slot_token_budget)

        # -- paged-NATIVE hot path gating ---------------------------------
        # attention families run decode/chunk straight against the page
        # pools (zero-gather); pure-SSM families carry no paged leaves (the
        # state path is already gather-free) and ring layouts store their
        # window as per-slot state — both keep the dense-view step.
        # ``paged_native=False`` forces the dense-gather step on an
        # attention family: the benchmark/test ORACLE the native path is
        # verified bit-identical (and cheaper) against.
        native_ok = (mode == "continuous" and kvcache_impl == "paged"
                     and self.api.decode_step_paged is not None and not ring)
        if paged_native is None:
            paged_native = native_ok
        elif paged_native and not native_ok:
            raise ValueError(
                "paged_native requires mode='continuous', "
                "kvcache_impl='paged', a family with paged-native entry "
                f"points (not {cfg.family!r} with ring="
                f"{ring}) — pure-SSM families and ring (sliding-window) "
                "layouts keep the state/dense-view path")
        self.paged_native = bool(paged_native)
        # model-parallel service mesh (``None`` = one device): arenas are
        # born sharded over it and every arena step is jitted under it
        self.mesh = mesh
        if chunked_prefill is None:
            chunked_prefill = (mode == "continuous"
                               and kvcache_impl == "paged" and not ring)
        elif chunked_prefill:
            if mode != "continuous" or kvcache_impl != "paged":
                raise ValueError("chunked_prefill requires "
                                 "mode='continuous' + kvcache_impl='paged'")
            if ring:
                raise ValueError("chunked_prefill does not support ring "
                                 "(sliding-window) cache layouts")
        self.chunked_prefill = bool(chunked_prefill)
        # ring layouts silently took the one-shot path before; the fallback
        # is now an explicit, counted state (StepStats.oneshot_prefills)
        self.ring_fallback = bool(ring and mode == "continuous"
                                  and kvcache_impl == "paged"
                                  and not self.chunked_prefill)
        # explicit chunk sizes are validated, not silently rounded: the
        # chunk is the arena's scatter unit, so it must be a positive
        # multiple of the block size (mirrored by launch/serve.py's flags)
        explicit_chunk = (prefill_chunk if prefill_chunk is not None
                          else (plan.prefill_chunk or None))
        if explicit_chunk is not None:
            chunk = int(explicit_chunk)
            if chunk <= 0 or chunk % block_size:
                raise ValueError(
                    f"prefill_chunk must be a positive multiple of "
                    f"block_size={block_size}, got {chunk}")
        else:
            chunk = plan.prefill_chunk_tokens(block_size)
        self.prefill_chunk_tokens = min(chunk, self.slot_token_budget)
        self.chunk_buckets = self._derive_buckets(self.prefill_chunk_tokens)

        # -- prefix cache (radix shared-prefix KV reuse) ------------------
        if prefix_cache is None:
            knob = plan.prefix_cache
            explicit_prefix = False
        else:
            knob = (-1 if prefix_cache is True
                    else 0 if prefix_cache is False else int(prefix_cache))
            if knob < -1:
                raise ValueError(
                    f"prefix_cache must be -1 (category default), 0 "
                    f"(disabled) or a positive retention block count; got "
                    f"{knob}")
            explicit_prefix = knob != 0
        cacheable = (mode == "continuous" and kvcache_impl == "paged"
                     and self.chunked_prefill
                     and cfg.family in PREFIX_CACHEABLE_FAMILIES)
        if explicit_prefix and not cacheable:
            raise ValueError(
                "prefix_cache requires mode='continuous', "
                "kvcache_impl='paged', chunked prefill (so hits resume "
                f"mid-prompt) and a family in {PREFIX_CACHEABLE_FAMILIES} "
                "(paged KV must be a pure function of prompt tokens); got "
                f"family={cfg.family!r}, mode={mode!r}, "
                f"kvcache_impl={kvcache_impl!r}, "
                f"chunked_prefill={self.chunked_prefill}")
        self._prefix_knob = knob
        self.prefix_cache_enabled = bool(cacheable and knob != 0)

        # -- speculative decoding (draft/verify) --------------------------
        # latency-category services trade draft FLOPs for fewer serial
        # target launches: a small draft model proposes k tokens, the
        # target scores all k+1 in ONE fused verify launch
        # (api.verify_step_paged through the existing chunk-attention
        # kernels).  Greedy acceptance keeps tokens bit-identical to the
        # non-speculative engine; stochastic acceptance is exact
        # leave-one-out rejection sampling (serving/sampler.py).
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError("draft_params and draft_cfg come together")
        have_draft = draft_params is not None
        knob_k = (plan.resolved_speculate(have_draft) if speculate is None
                  else int(speculate))
        if knob_k > 0 and not have_draft:
            raise ValueError(
                f"speculate={knob_k} requires a draft model (draft_params "
                "+ draft_cfg); the category default degrades to 0 without "
                "one, an explicit ask does not")
        if knob_k > 0:
            draft_ring = (draft_cfg.sliding_window is not None
                          and draft_cfg.sliding_window
                          < self.slot_token_budget)
            spec_ok = (mode == "continuous" and kvcache_impl == "paged"
                       and self.paged_native and self.chunked_prefill
                       and cfg.family in PREFIX_CACHEABLE_FAMILIES
                       and draft_cfg.family == cfg.family
                       and draft_cfg.vocab_size == cfg.vocab_size
                       and self.api.verify_step_paged is not None
                       and not draft_ring)
            if not spec_ok:
                raise ValueError(
                    "speculative decoding requires mode='continuous', "
                    "kvcache_impl='paged', paged_native, chunked_prefill, "
                    f"a family in {PREFIX_CACHEABLE_FAMILIES} with a "
                    "verify entry point, and a same-family same-vocab "
                    "non-ring draft; got "
                    f"family={cfg.family!r}/{draft_cfg.family!r}, "
                    f"vocab={cfg.vocab_size}/{draft_cfg.vocab_size}, "
                    f"mode={mode!r}, kvcache_impl={kvcache_impl!r}, "
                    f"paged_native={self.paged_native}, "
                    f"chunked_prefill={self.chunked_prefill}, "
                    f"draft_ring={draft_ring}")
        self.speculate_k = knob_k
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.draft_api: Optional[ModelApi] = (
            model_api(draft_cfg) if have_draft else None)
        self._draft_chunk_fns: Dict[Any, Callable] = {}
        self._draft_decode_fn = None
        self._verify_fn = None
        self.draft_steps = 0         # fused draft decode invocations
        self.verify_launches = 0     # fused verify invocations
        self.accepted_tokens = 0     # target tokens committed by verify
        self.spec_degraded = 0       # speculation fallbacks (alloc/park)
        self.verify_traces = 0       # XLA (re)compilations of verify
        self.draft_decode_traces = 0
        self.draft_prefill_traces = 0
        self.draft_prefill_tokens = 0

        # -- fault tolerance (crash evacuation, core/faults.py) -----------
        self.evacuations = 0         # crash evacuations of this runtime
        self.evacuated_requests = 0  # requests stripped out across them
        self._evacuated_pending = 0  # delta reported by the next StepStats

        # -- n>1 parallel sampling (refcounted prompt-block forks) --------
        self.forks_spawned = 0
        self.fork_shortfall = 0
        self._sibling_refs: Dict[int, int] = {}   # rid -> live siblings
        self.n_samples_cap = (plan.resolved_n_samples()
                              if (mode == "continuous"
                                  and kvcache_impl == "paged"
                                  and self.chunked_prefill) else 1)
        api = self.api

        if prefill_fn is None:
            def _prefill(p, b, cs):
                self.prefill_traces += 1    # runs at trace time only
                return api.prefill(p, cfg, b, cache_size=cs, impl=impl)
            prefill_fn = jax.jit(_prefill, static_argnums=(2,))
        if decode_fn is None:
            def _decode(p, t, c):
                self.decode_traces += 1     # runs at trace time only
                return api.decode_step(p, cfg, t, c, impl=impl)
            decode_fn = jax.jit(_decode)
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self._impl = impl

    @property
    def slot_token_budget(self) -> int:
        """Cache tokens one arena slot can hold (block-rounded
        ``max_seq_len``); a request's prompt + family extras + max_new
        must fit."""
        blocks = max(1, -(-self.max_seq_len // self.block_size))
        return blocks * self.block_size

    def _derive_buckets(self, chunk: int):
        """Static chunk shapes the engine compiles: power-of-two multiples
        of ``block_size`` up to the category's chunk size.  The smallest
        bucket is always one block, so a final partial chunk never
        overshoots the slot budget."""
        buckets, b = [], self.block_size
        while b < chunk:
            buckets.append(b)
            b *= 2
        buckets.append(chunk)
        return tuple(sorted(set(buckets)))

    def _pick_bucket(self, remaining: int,
                     budget: Optional[int] = None) -> Optional[int]:
        """Largest bucket that fits the remaining prompt, else the
        smallest (one-block) bucket for the final partial chunk — never
        exceeding the step's remaining token ``budget`` (None when the
        budget cannot afford even the smallest bucket: the caller defers
        the chunk to the next step, keeping the per-step prefill spend at
        or under ``prefill_chunk`` tokens)."""
        affordable = (self.chunk_buckets if budget is None else
                      [b for b in self.chunk_buckets if b <= budget])
        if not affordable:
            return None
        for b in reversed(affordable):
            if b <= remaining:
                return b
        return affordable[0]

    # -- queue ------------------------------------------------------------
    def submit(self, req: GenerationRequest, now: float = 0.0) -> None:
        if self.kvcache_impl == "paged" and self.mode == "continuous":
            # reject over-budget requests at the door: raising later, mid-
            # admission, would drop the composed batch's other members and
            # leak their session pins
            total = (len(req.tokens) + self._extra_cache_tokens()
                     + req.max_new_tokens)
            if total > self.slot_token_budget:
                raise ValueError(
                    f"request {req.rid} needs {total} cache tokens > "
                    f"per-slot budget {self.slot_token_budget}; raise "
                    f"max_seq_len")
        if self.plan.sticky and req.stream:
            self._session_refs[req.stream] = \
                self._session_refs.get(req.stream, 0) + 1
        if self.metrics is not None or self.trace.enabled:
            self._submit_wall[req.rid] = time.perf_counter()
        tr = self.trace
        if tr.enabled:
            tid = str(req.rid)
            tr.begin(self.obs_name, tid, "request",
                     prompt_tokens=len(req.tokens),
                     max_new=req.max_new_tokens, n_samples=req.n_samples)
            tr.begin(self.obs_name, tid, "queued")
        self.composer.add(QueuedItem(payload=req, stream=req.stream,
                                     enqueued_s=now, rid=req.rid))

    def pending(self) -> int:
        return len(self.composer)

    def in_flight(self) -> int:
        return sum(g.live for g in self.groups.values())

    def total_slots(self) -> int:
        return sum(self._group_slots(g) for g in self.groups.values())

    # -- shared helpers ---------------------------------------------------
    def _pad_prompts(self, reqs: Sequence[GenerationRequest]):
        L = max(len(r.tokens) for r in reqs)
        toks = np.zeros((len(reqs), L), np.int32)
        lens = np.zeros((len(reqs),), np.int32)
        for i, r in enumerate(reqs):
            toks[i, L - len(r.tokens):] = r.tokens   # left-pad
            lens[i] = len(r.tokens)
        return jnp.asarray(toks), lens

    def _build_batch(self, reqs: Sequence[GenerationRequest], toks):
        batch: Dict[str, Any] = {"tokens": toks}
        if self.cfg.family in ("audio", "vlm"):
            embs = [r.extras["embeddings"] for r in reqs]
            batch["embeddings"] = jnp.asarray(np.stack(embs))
        return batch

    def _extra_cache_tokens(self) -> int:
        """Cache positions a request consumes beyond its text prompt: the
        VLM family's image prefix rides along in the decoder cache (its
        ``prefill`` budgets ``cache_size`` in TEXT tokens and adds the
        prefix itself)."""
        return self.cfg.prefix_len if self.cfg.family == "vlm" else 0

    def _req_seed(self, req: GenerationRequest) -> int:
        """The request's sampling-stream seed (``rid`` unless the caller
        pinned one) — with the per-slot counter streams below, a request's
        tokens are a pure function of this seed, never of which other
        requests share its fused batch."""
        return req.rid if req.seed is None else int(req.seed)

    def _sample(self, logits, seeds, sample_ids, offsets,
                live=None, occupancy=None, stream: int = STREAM_DECODE):
        """Per-slot counter-based sampling (the batch-composition bugfix).

        The old path split ``self._key`` once per fused step and drew the
        whole batch from the split — so every request's tokens depended on
        HOW MANY steps the engine had taken and WHICH slots were live:
        admitting an unrelated request changed another request's output,
        and park/resume shifted the stream.  Now each row's key is
        ``fold_in(fold_in(fold_in(fold_in(base, seed), sample_idx),
        stream), offset)`` — a pure function of the request's own
        identity and its emitted length at the draw — so tokens are
        bit-identical alone, in any batch mix, and across park/resume or
        speculative on/off (greedy never touches a key at all)."""
        return sample_per_slot(
            logits, self._key, np.asarray(seeds, np.uint32),
            np.asarray(sample_ids, np.uint32),
            np.asarray(offsets, np.uint32), self.sampler, stream=stream,
            live=live, occupancy=occupancy)

    def _obs_admitted(self, req: GenerationRequest, group: int,
                      next_span: str, **args) -> None:
        """Observability at an admission transition: record the measured
        queue wait (submit -> first admission; resumes keep the
        original), close the request's innermost open span (``queued``,
        or ``parked`` on a resume) and open the next lifecycle span."""
        if self._submit_wall:
            t = self._submit_wall.pop(req.rid, None)
            if t is not None:
                self._queue_wait[req.rid] = max(
                    0.0, time.perf_counter() - t)
        tr = self.trace
        if tr.enabled:
            tid = str(req.rid)
            tr.end(self.obs_name, tid, group=group)
            tr.begin(self.obs_name, tid, next_span, **args)

    def _slot_tid(self, s: _Slot) -> str:
        """The slot's trace timeline: the request id, with n>1 sampling
        forks on their own ``rid.sample`` lane."""
        return (str(s.req.rid) if s.sample_idx == 0
                else f"{s.req.rid}.{s.sample_idx}")

    def _finish_request(self, req: GenerationRequest, group: int) -> None:
        """Session-pin bookkeeping + user hook, fired whenever a request
        leaves the data plane (slot eviction or sync-batch completion)."""
        self._submit_wall.pop(req.rid, None)
        self._queue_wait.pop(req.rid, None)
        if self.trace.enabled:
            # balanced no matter where the request died: close() ends
            # every still-open span (a shed request's verdict close
            # already emptied the stack, making this a no-op)
            self.trace.close(self.obs_name, str(req.rid), outcome="served")
        if self.plan.sticky and req.stream:
            left = self._session_refs.get(req.stream, 1) - 1
            if left <= 0:
                self._session_refs.pop(req.stream, None)
                self.router.release(req.stream)
            else:
                self._session_refs[req.stream] = left
        if self.on_evict is not None:
            self.on_evict(req, group)

    def _finish_sibling(self, req: GenerationRequest, group: int) -> None:
        """Eviction-side bookkeeping for n>1 sampling: a forked request's
        session pins and eviction hook fire once — when its LAST sibling
        slot leaves the data plane, not once per sample."""
        refs = self._sibling_refs.get(req.rid)
        if refs is None:
            self._finish_request(req, group)
            return
        if refs <= 1:
            self._sibling_refs.pop(req.rid, None)
            self._finish_request(req, group)
        else:
            self._sibling_refs[req.rid] = refs - 1

    def _note_service_time(self, res: GenerationResult) -> None:
        if res.sample == 0:
            # forks carry the primary's prefill_s but paid no prefill
            # compute: count the wall time once or the calibration's
            # prefill_token_s numerator double-counts
            self.prefill_seconds += max(0.0, res.prefill_s)
        t = max(1e-6, res.prefill_s + max(0.0, res.decode_s))
        self._service_ewma_s = (t if self._service_ewma_s == 0.0
                                else 0.8 * self._service_ewma_s + 0.2 * t)

    def queue_time_estimate(self) -> float:
        """Expected wait before a newly queued request starts decoding —
        the handler's queue-time feedback signal (Eq. 1 exclusion uses
        it to skip backlogged peers).  Under chunked prefill the queued
        PROMPT TOKENS matter too: the (b2) phase drains at most one chunk
        budget per group per step, so a prompt-heavy queue is priced as
        the extra request-waves those chunks occupy."""
        if self._service_ewma_s <= 0.0:
            return 0.0
        waves = self.pending() / max(1, self.total_slots())
        if self.chunked_prefill and self.prefill_chunk_tokens > 0:
            # queued prompts PLUS admitted-but-unconsumed ones: a long
            # prompt leaves the composer at alloc time but keeps eating
            # (b2) budget until its last chunk lands
            queued = self.composer.pending_prefill_tokens()
            if self.prefix_cache_enabled:
                # cached-token term: the observed hit-rate EWMA predicts
                # the fraction of QUEUED prompt tokens the prefix cache
                # will serve without compute, so the handler's queue-time
                # signal doesn't overprice repeated-prefix (frequency)
                # traffic.  In-flight unconsumed tokens are already
                # post-hit (slots admit with consumed = hit_tokens), so
                # they are not discounted again.
                queued *= max(0.0, 1.0 - self._prefix_hit_ewma)
            backlog = queued + self._unconsumed_prompt_tokens()
            chunk_steps = backlog / (self.prefill_chunk_tokens
                                     * max(1, len(self.groups)))
            waves += chunk_steps / max(1, self.total_slots())
        return waves * self._service_ewma_s

    def _unconsumed_prompt_tokens(self) -> int:
        """Prompt tokens of in-flight slots still awaiting their chunks."""
        return sum(len(s.req.tokens) - s.consumed
                   for g in self.groups.values() for s in g.slots
                   if s.prefilling)

    # ------------------------------------------------------------------
    # continuous mode: slot admit / fused decode / evict
    # ------------------------------------------------------------------
    def _free_slots(self) -> int:
        return sum(max(0, self._group_slots(g) - g.live)
                   for g in self.groups.values())

    def _evict(self, group: int, state: _GroupState,
               now: float) -> List[GenerationResult]:
        """(a) Release every slot whose request finished.  Paged: a pure
        free-list operation per slot.  Dense: compact the cache batch axis
        with select_slots (a whole-batch copy)."""
        if not state.slots:
            return []
        keep = [i for i, s in enumerate(state.slots) if not s.done]
        if len(keep) == len(state.slots):
            return []
        results = []
        for s in state.slots:
            if not s.done:
                continue
            res = GenerationResult(
                rid=s.req.rid, tokens=np.asarray(s.emitted, np.int32),
                prefill_s=s.prefill_s,
                decode_s=max(0.0, s.finish_wall - s.decode_start_wall),
                group=group, admitted_s=s.admitted_s, finished_s=now,
                decode_steps=s.steps, sample=s.sample_idx)
            results.append(res)
            self._note_service_time(res)
            self.admission.observe(res)
            if self.trace.enabled:
                self.trace.end(self.obs_name, self._slot_tid(s),
                               tokens=len(s.emitted), steps=s.steps)
            if self.metrics is not None:
                n = len(s.emitted)
                self.metrics.observe_request(
                    self.obs_name,
                    ttft_s=max(0.0, s.decode_start_wall - s.submit_wall),
                    tpot_s=(res.decode_s / (n - 1)) if n > 1 else None,
                    queue_wait_s=self._queue_wait.get(s.req.rid, 0.0),
                    new_tokens=n)
            if state.arena is not None:
                if s.spec and state.draft is not None:
                    state.draft.free(s.slot_id)
                    s.spec = False
                if state.prefix is not None and not s.prefilling:
                    # the slot will never write again: its partial tail
                    # block's prompt content is final, so it can join the
                    # index (sharers mask the generated tokens past the
                    # entry's valid count and COW before writing)
                    state.prefix.insert(
                        s.req.tokens,
                        state.arena._block_tables[s.slot_id])
                state.arena.free(s.slot_id)
            self._finish_sibling(s.req, group)
        state.slots = [state.slots[i] for i in keep]
        if state.arena is None:
            state.cache = (kvcache.select_slots(state.cache, keep)
                           if keep else None)
            if keep:                 # compaction re-materialized the batch
                self.whole_cache_copies += 1
                self.admission_copy_bytes += kvcache.cache_bytes(state.cache)
        return results

    def _group_slots(self, state: _GroupState) -> int:
        """Decode slots of one DP group: its arena's capacity once built
        (the plan's ``bs`` clipped to device memory), the plan's before."""
        return (state.arena.capacity if state.arena is not None
                else self.plan.max_in_flight)

    def _new_arena(self, capacity: int) -> KVArena:
        return KVArena(self.cfg, self.api.init_cache, capacity=capacity,
                       max_seq_len=self.max_seq_len,
                       block_size=self.block_size,
                       pool_blocks=self.pool_blocks, kv_dtype=self.kv_dtype,
                       mesh=self.mesh)

    def _fit_capacity(self) -> int:
        """Slots for a new group arena: the plan's ``max_in_flight``
        clipped to what the device's memory holds after everything
        already on it (weights, other arenas) and the fused steps' own
        temporaries — ``plan.bs`` itself stays what EPARA chose.  Backends
        that report no memory limit (CPU) and explicit ``pool_blocks``
        keep the plan's count.  Raises when not even one slot of
        ``max_seq_len`` fits: shrinking the per-slot budget would change
        which requests the service accepts."""
        want = self.plan.max_in_flight
        free = self._free_device_bytes()
        if free is None or self.pool_blocks is not None:
            return want
        # DP groups still without an arena share what is left evenly
        free //= sum(1 for g in self.groups.values() if g.arena is None)
        probe = self._new_arena(1)
        per_slot = probe.device_slot_bytes()
        if self.speculate_k > 0:     # a draft arena pairs every slot
            per_slot += self._new_draft_arena().device_slot_bytes()
        # the temporaries at ``cap`` slots bound those of any smaller
        # count, so ``min(cap, fit)`` fits; when they leave no slot, or
        # the compiler itself finds the program too big for the device,
        # step down and compile again
        cap = min(want, free // per_slot)
        while cap >= 1:
            try:
                scratch = self._step_scratch_bytes(probe, cap)
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                cap = cap * 7 // 8
                continue
            fit = (free - scratch) // per_slot
            if fit >= 1:
                cap = min(cap, fit)
                break
            cap = cap * 7 // 8
        if cap < 1:
            raise MemoryError(
                f"{self.cfg.name}: one arena slot of max_seq_len="
                f"{self.max_seq_len} needs {per_slot} device bytes, but "
                f"only {free} are free after the weights (less the fused "
                f"steps' temporaries)")
        return int(cap)

    def _free_device_bytes(self) -> Optional[int]:
        """Least free memory over the devices this runtime's arena lives
        on, or ``None`` where the backend reports no limit (CPU)."""
        devices = (list(self.mesh.devices.flat) if self.mesh is not None
                   else [jax.devices()[0]])
        stats = [d.memory_stats() or {} for d in devices]
        if any("bytes_limit" not in st for st in stats):
            return None
        return min(st["bytes_limit"] - st["bytes_in_use"] for st in stats)

    def _new_draft_arena(self, capacity: int = 1) -> KVArena:
        """A draft-model arena.  Its KV stays native precision: the
        proposals are re-scored by the target anyway, but int8 would
        change WHICH tokens get proposed run-to-run."""
        return KVArena(self.draft_cfg, self.draft_api.init_cache,
                       capacity=capacity, max_seq_len=self.max_seq_len,
                       block_size=self.block_size, mesh=self.mesh,
                       kv_dtype="bf16")

    def _step_scratch_bytes(self, probe: KVArena, capacity: int) -> int:
        """Device bytes the serving programs need beside the arena at
        ``capacity`` slots, from ``memory_analysis`` of the programs
        compiled against ``probe``'s layout without allocating the full
        arena: the code of every program that stays loaded (the fused
        decode step, a chunk step per bucket, the samplers and, when
        speculating, the draft decode and verify steps), plus the most
        any one call allocates beyond its donated arguments (temporaries
        and un-aliased outputs, with the logits it samples from still
        live) — the one-shot prefill of a longest prompt included where
        prompts are not chunked."""
        cap, bps = capacity, probe.blocks_per_slot

        def arena_args(arena):
            grown = lambda a: jax.ShapeDtypeStruct(
                (a.shape[0], cap, *a.shape[2:]), a.dtype,
                sharding=a.sharding)
            return (arena.pool_structs(cap * bps + 1),
                    [grown(st) for st in arena.state],
                    jax.ShapeDtypeStruct((cap,), jnp.int32,
                                         sharding=arena.lens.sharding))

        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        u32 = jax.ShapeDtypeStruct((cap,), jnp.uint32)
        flags = jax.ShapeDtypeStruct((cap,), bool)
        counters = ("decode_traces", "prefill_traces", "verify_traces",
                    "draft_decode_traces")
        saved = [getattr(self, c) for c in counters]
        pools = arena_args(probe)
        progs = []               # (lowered, resident copies, live bytes)
        try:
            decode = self._build_paged_decode_fn(probe).lower(
                self.params, i32(cap), *pools, flags, i32(cap, bps))
            logits = decode.out_info[0]
            nbytes = lambda a: math.prod(a.shape) * a.dtype.itemsize
            progs.append((decode, 1, 0))
            if self.chunked_prefill:
                T = self.chunk_buckets[-1]
                progs.append((self._build_chunk_fn(probe, T, False).lower(
                    self.params, i32(1, T), None, *pools, i32(), i32(bps),
                    i32()), len(self.chunk_buckets), 0))
            else:
                # one program per prompt length: the longest one's
                # temporaries bound the others'
                n = probe.slot_tokens - self._extra_cache_tokens()
                if (n > 1 and hasattr(self.prefill_fn, "lower")
                        and self.cfg.family not in ("audio", "vlm")):
                    progs.append((self.prefill_fn.lower(
                        self.params, {"tokens": i32(1, n - 1)}, n), 1, 0))
            # decode's sampler and a first token's (or a draft's)
            progs.append((sample_per_slot.lower(
                logits, self._key, u32, u32, u32, self.sampler,
                live=flags, occupancy=flags), 2, nbytes(logits)))
            if self.speculate_k > 0:
                k = self.speculate_k
                draft = self._new_draft_arena()
                progs.append((self._arena_jit(self._paged_decode_pure(
                    draft, api=self.draft_api, cfg=self.draft_cfg,
                    native=True, counter="draft_decode_traces"), draft,
                    (2, 3, 4)).lower(
                        self.draft_params, i32(cap), *arena_args(draft),
                        flags, i32(cap, bps)), 1, 0))
                dlogits = jax.ShapeDtypeStruct(
                    (cap, k, logits.shape[-1]), logits.dtype)
                progs.append((self._build_verify_fn(probe).lower(
                    self.params, i32(cap, k + 1), dlogits, i32(cap, k),
                    *pools, flags, u32, u32, u32, i32(cap, bps), flags),
                    1, nbytes(dlogits)))
            mems = [(lo.compile().memory_analysis(), n, live)
                    for lo, n, live in progs]
        finally:
            for c, v in zip(counters, saved):
                setattr(self, c, v)
        code = sum(m.generated_code_size_in_bytes * n for m, n, _ in mems)
        return code + max(m.temp_size_in_bytes + m.output_size_in_bytes
                          - m.alias_size_in_bytes + live
                          for m, _, live in mems)

    def _ensure_arena(self, state: _GroupState) -> KVArena:
        if state.arena is None:
            state.arena = self._new_arena(self._fit_capacity())
            if self.prefix_cache_enabled:
                state.prefix = RadixPrefixCache(
                    state.arena,
                    retention_blocks=self.plan.prefix_cache_blocks(
                        state.arena.pool_blocks, override=self._prefix_knob))
        return state.arena

    def _admit_one(self, req: GenerationRequest, group: int,
                   state: _GroupState, now: float,
                   pending_cows: Optional[List] = None) -> bool:
        """(b) Claim a slot for one admission.  Chunked paged: just an
        arena ``alloc`` — the prompt is prefilled chunk by chunk in the
        (b2) phase, so admission itself never stalls the step.  Unchunked
        paged: one-shot prefill + page scatter.  Dense: one-shot prefill +
        kvcache.merge (re-materializes everything).  Returns False when
        the arena is out of blocks (caller requeues).

        ``pending_cows`` collects this wave's divergence copy-on-writes
        (partial-tail prefix hits) instead of dispatching one jitted
        single-block copy per admission: ``_admit`` flushes them in ONE
        batched ``arena.cow_blocks`` scatter after the wave — the common
        templated-prompt burst (several admissions sharing one template)
        pays one dispatch, not one per member."""
        extra = self._extra_cache_tokens()
        if self.kvcache_impl == "paged":
            arena = self._ensure_arena(state)
            total = len(req.tokens) + extra + req.max_new_tokens
            if total > arena.slot_tokens:
                raise ValueError(
                    f"request {req.rid} needs {total} tokens > per-slot "
                    f"budget {arena.slot_tokens}; raise max_seq_len")
            entry = self.admission.parked.get(req.rid)
            if entry is not None:
                return self._resume_parked(req, state, entry, total,
                                           pending_cows)
            if self.chunked_prefill:
                # prefix-cache lookup: stitch the longest cached prefix
                # into the new slot's block table; chunked prefill then
                # starts AFTER the hit boundary
                hit: Optional[PrefixHit] = None
                pc = state.prefix
                looked = pc is not None and len(req.tokens) > 1
                if looked:
                    h = pc.lookup(req.tokens)
                    if h.tokens > 0:
                        hit = h
                # blocks already promised to this wave's deferred COWs
                # must stay claimable until the flush
                reserved = len(pending_cows) if pending_cows else 0
                if hit is not None and hit.partial_valid:
                    # a partial-tail share ALWAYS needs its divergence COW
                    # (the first computed token lands inside that block),
                    # so admit only with headroom for the copy; under a
                    # tight pool degrade to the full-block hit instead of
                    # failing mid-step
                    if not arena.can_alloc(total, shared=hit.blocks,
                                           reserve=1 + reserved):
                        hit = (PrefixHit(blocks=hit.blocks[:-1],
                                         tokens=hit.full_blocks
                                         * arena.block_size,
                                         full_blocks=hit.full_blocks,
                                         partial_valid=0)
                               if hit.full_blocks else None)
                shared = hit.blocks if hit is not None else ()
                if not arena.can_alloc(total, shared=shared,
                                       reserve=reserved):
                    return False
                slot_id = arena.alloc(total, shared=shared)
                if hit is not None:
                    arena.set_len(slot_id, hit.tokens)
                    if hit.partial_valid:
                        # divergence copy, deferred to the wave's batched
                        # flush (headroom was reserved above;
                        # ensure_writable in the chunk and decode paths
                        # stays as an invariant guard)
                        if pending_cows is not None:
                            pending_cows.append((slot_id, hit.full_blocks))
                        else:
                            arena.cow_block(slot_id, hit.full_blocks)
                            self.admission_copy_bytes += (
                                arena.block_size * arena.token_bytes)
                else:
                    arena.reset_len(slot_id)
                slot = _Slot(req, None, prefill_s=0.0,
                             admit_wall=time.perf_counter(),
                             admitted_s=now, slot_id=slot_id,
                             submit_wall=self._submit_wall.get(req.rid))
                if hit is not None:
                    slot.consumed = hit.tokens
                if looked:
                    pc.record(hit, len(req.tokens))
                if pc is not None:
                    # EWMA over ALL admissions (1-token prompts count as
                    # misses) so the queue-time discount stays honest
                    frac = ((hit.tokens / len(req.tokens))
                            if hit is not None else 0.0)
                    self._prefix_hit_ewma = (0.8 * self._prefix_hit_ewma
                                             + 0.2 * frac)
                state.slots.append(slot)
                self._obs_admitted(req, group, "prefill",
                                   hit_tokens=slot.consumed)
                return True
            if not arena.can_alloc(total):
                return False
            # cache_size is budgeted in text tokens; family extras (VLM
            # prefix) ride along so the model-built cache lands exactly on
            # the arena's slot_tokens sequence axis
            cache_size = arena.slot_tokens - extra
        else:
            cache_size = int(len(req.tokens) + req.max_new_tokens)

        submit_wall = self._submit_wall.get(req.rid)
        self._obs_admitted(req, group, "prefill", oneshot=True)
        t0 = time.perf_counter()
        toks, _ = self._pad_prompts([req])
        batch = self._build_batch([req], toks)
        logits, cache = self.prefill_fn(self.params, batch, cache_size)
        first_dev = self._sample(logits, [self._req_seed(req)], [0], [0])
        with self._wait("oneshot", tokens=len(req.tokens)):
            first = int(np.asarray(first_dev)[0])
            jax.block_until_ready(logits)
        t1 = time.perf_counter()
        self.oneshot_prefills += 1
        self.prefill_tokens_computed += len(req.tokens)

        if self.kvcache_impl == "paged":
            slot_id = arena.alloc(total)
            self.admission_copy_bytes += arena.write_prefill(
                slot_id, cache, prompt_len=len(req.tokens) + extra)
        else:
            slot_id = len(state.slots)
            cache = kvcache.with_lens(cache, kvcache.lens(cache))
            self.admission_copy_bytes += kvcache.cache_bytes(cache)
            if state.cache is None:
                state.cache = cache
            else:
                # the merge copies the entire live batch to admit one row
                self.admission_copy_bytes += kvcache.cache_bytes(state.cache)
                self.whole_cache_copies += 1
                state.cache = kvcache.merge([state.cache, cache])
        state.slots.append(_Slot(req, first, prefill_s=t1 - t0,
                                 admit_wall=t0, admitted_s=now,
                                 slot_id=slot_id, decode_start_wall=t1,
                                 submit_wall=submit_wall))
        tr = self.trace
        if tr.enabled:
            tid = str(req.rid)
            tr.end(self.obs_name, tid, tokens_computed=len(req.tokens))
            tr.instant(self.obs_name, tid, "first_token")
            tr.begin(self.obs_name, tid, "decode")
        return True

    def _resume_parked(self, req: GenerationRequest, state: _GroupState,
                       entry: ParkedEntry, total: int,
                       pending_cows: Optional[List] = None) -> bool:
        """Re-admit a preempted request onto its parked blocks: alloc with
        ``shared=blocks`` re-increfs every block (a 100% prefix hit over
        the WHOLE parked content, generated tokens included), then the
        parked hold drops — net refcounts unchanged, zero prefill, zero
        copies.  The slot resumes at the exact device length and emitted
        tokens of park time, so greedy continuation is bit-identical."""
        arena = state.arena
        reserved = len(pending_cows) if pending_cows else 0
        if not arena.can_alloc(total, shared=entry.blocks,
                               reserve=reserved):
            return False
        slot_id = arena.alloc(total, shared=entry.blocks)
        arena.release_parked(entry.blocks)
        arena.set_len(slot_id, entry.cache_len)
        slot = _Slot(req, None, prefill_s=entry.prefill_s,
                     admit_wall=entry.admit_wall,
                     admitted_s=entry.admitted_s, slot_id=slot_id,
                     submit_wall=entry.submit_wall)
        slot.prefilling = False
        slot.emitted = list(entry.emitted)
        slot.decode_start_wall = entry.decode_start_wall
        slot.steps = entry.steps
        slot.consumed = entry.consumed
        state.slots.append(slot)
        self.admission.pop_parked(req.rid)
        self.admission.note_resume()
        if state.prefix is not None:
            # a resume is the prefix cache's best case: the entire parked
            # content (prompt AND generated KV) is served from resident
            # blocks — count it so the hit telemetry reflects the reuse
            state.prefix.note_resume(entry.cache_len)
        self._obs_admitted(req, entry.group, "decode", resumed=True)
        return True

    def _park_slot(self, group: int, state: _GroupState, s: _Slot,
                   now: float) -> None:
        """Preempt one live decode slot by block-table parking: freeze its
        blocks in the arena (KV stays resident, references held by the
        ``ParkedEntry``), free the slot, and re-queue the request — its
        later compose resumes via ``_resume_parked``."""
        arena = state.arena
        if s.spec and state.draft is not None:
            # the draft cache is disposable state (re-derivable from the
            # tokens) but re-chasing it after resume isn't worth the
            # chunks: a parked request resumes NON-speculative.  Greedy
            # spec-on/spec-off is bit-identical, so the degradation is
            # invisible in the tokens — only in the telemetry.
            state.draft.free(s.slot_id)
            s.spec = False
            s.draft_len = 0
            self.spec_degraded += 1
        with self._wait("park", tokens=len(s.emitted)):
            cache_len = int(arena.lens[s.slot_id])
        entry = ParkedEntry(
            req=s.req, group=group,
            blocks=[], cache_len=cache_len,
            emitted=list(s.emitted), consumed=s.consumed, steps=s.steps,
            prefill_s=s.prefill_s, admit_wall=s.admit_wall,
            decode_start_wall=s.decode_start_wall,
            admitted_s=s.admitted_s, parked_s=now,
            submit_wall=s.submit_wall)
        entry.blocks = arena.park(s.slot_id)
        state.slots.remove(s)
        self.admission.note_park(entry)
        tr = self.trace
        if tr.enabled:
            tid = str(s.req.rid)
            tr.end(self.obs_name, tid, reason="park",
                   tokens=len(s.emitted))
            tr.begin(self.obs_name, tid, "parked")
        self.composer.add(QueuedItem(payload=s.req, stream=s.req.stream,
                                     enqueued_s=now, rid=s.req.rid))

    def _maybe_preempt(self, now: float) -> None:
        """Park the laziest live decode slot when the most urgent pending
        request would otherwise miss its deadline waiting.  One victim per
        step bounds churn; the controller's guard ensures the victim can
        afford the round trip."""
        ctrl = self.admission
        if not (ctrl.active and ctrl.preempt
                and self.kvcache_impl == "paged"):
            return
        if self._free_slots() > 0 or len(ctrl.parked) >= ctrl.max_parked:
            return
        head = self.composer.peek()
        if head is None:
            return
        urgent_slack = ctrl.slack(head.payload, now)
        if not 0.0 <= urgent_slack < float("inf"):
            return                   # doomed (shed next round) or lax
        if urgent_slack >= ctrl.wait_estimate(now):
            return                   # it can afford to wait its turn
        candidates = []
        for g, state in self.groups.items():
            arena = state.arena
            if arena is None or not arena.parkable:
                continue             # per-slot state can't survive parking
            for s in state.slots:
                if s.done or s.prefilling or s.req.rid == head.rid:
                    continue
                if s.req.rid in self._sibling_refs:
                    # n>1 siblings share one request identity: parking one
                    # fork would re-queue the rid while other samples keep
                    # decoding it — resume would then double-admit
                    continue
                candidates.append((ctrl.slot_slack(s, now),
                                   ctrl.remaining_estimate(s),
                                   (g, state, s)))
        victim = ctrl.pick_victim(urgent_slack, candidates)
        if victim is not None:
            self._park_slot(*victim, now)

    def _shed_rejected(self, now: float) -> List[AdmissionReject]:
        """Run the controller's shed pass and finalize each reject: parked
        blocks are released back to the arena (cached ones fall to the
        idle LRU), session pins drop, and the eviction hook fires — every
        shed request leaves the data plane carrying its verdict."""
        rejects: List[AdmissionReject] = []
        for item, verdict in self.admission.shed(now):
            req = item.payload
            entry = self.admission.pop_parked(item.rid)
            if entry is not None:
                self.groups[entry.group].arena.release_parked(entry.blocks)
            if self.trace.enabled:
                # the verdict lands on the outermost ("request") span;
                # _finish_request's defensive close then no-ops
                self.trace.close(self.obs_name, str(req.rid),
                                 verdict=verdict.name)
            self._finish_request(req, -1)
            rejects.append(AdmissionReject(req=req, verdict=verdict,
                                           now=now))
        return rejects

    def _take_evacuated(self) -> int:
        """Evacuations since the last step, folded into ``StepStats``."""
        n = self._evacuated_pending
        self._evacuated_pending = 0
        return n

    def evacuate(self, now: float = 0.0) -> List[GenerationRequest]:
        """Crash this runtime's data plane (``core/faults.py`` adversary):
        strip every queued, in-flight and parked request out and return
        them rid-deduplicated for resubmission elsewhere.  In-flight KV
        state is lost with the process — survivors must re-prefill (the
        radix prefix cache makes that cheap when they land back here after
        a restart, so the warm prefix index is deliberately NOT torn
        down).  PR 8's counter-stream sampling makes the resubmitted
        request's tokens bit-identical on any replica, which is what lets
        recovery re-run prefill without corrupting the output."""
        out: Dict[int, GenerationRequest] = {}
        # (1) queued work — includes rids _park_slot re-queued
        for item, _ in self.composer.shed(lambda item: True):
            req = item.payload
            out.setdefault(req.rid, req)
        # (2) live slots: free draft/paged state per slot, then drop the
        # whole batch.  No prefix insert — the slot died mid-flight and
        # resubmission re-prefills from the index as it stands.  Slot rids
        # are finished HERE (via the sibling refcount, once per rid) and
        # skipped in the final pass; queued/parked rids never overlap
        # live slots, so no rid is finished twice.
        slot_rids: set = set()
        for group, state in self.groups.items():
            for s in state.slots:
                if s.spec and state.draft is not None:
                    state.draft.free(s.slot_id)
                    s.spec = False
                if state.arena is not None:
                    state.arena.free(s.slot_id)
                if self.trace.enabled:
                    self.trace.close(self.obs_name, self._slot_tid(s),
                                     outcome="evacuated")
                out.setdefault(s.req.rid, s.req)
                slot_rids.add(s.req.rid)
                self._finish_sibling(s.req, group)
            state.slots = []
            if state.arena is None:
                state.cache = None
        # (3) parked entries: their frozen blocks go back to the arena
        # (the rid itself is already in ``out`` via the composer drain)
        for rid in list(self.admission.parked):
            entry = self.admission.pop_parked(rid)
            if entry is None:
                continue
            arena = self.groups[entry.group].arena
            if arena is not None:
                arena.release_parked(entry.blocks)
            out.setdefault(entry.req.rid, entry.req)
        for req in out.values():
            if req.rid in slot_rids:
                continue
            if self.trace.enabled:
                self.trace.close(self.obs_name, str(req.rid),
                                 outcome="evacuated")
            self._finish_request(req, -1)
        self.evacuations += 1
        self.evacuated_requests += len(out)
        self._evacuated_pending += len(out)
        return list(out.values())

    def _route_admission(self, item: QueuedItem) -> Optional[int]:
        """Pick a DP group with a free slot; sticky sessions must land on
        their pinned group or wait.  A parked request is pinned harder
        still: its frozen blocks are physical ids in ONE group's arena."""
        pg = self.admission.parked_group(item.rid)
        if pg is not None:
            pgs = self.groups[pg]
            return pg if pgs.live < self._group_slots(pgs) else None
        g = self.router.route(session=item.stream)
        if self.groups[g].live < self._group_slots(self.groups[g]):
            return g
        if self.plan.sticky and item.stream:
            return None          # session pinned to a full group: requeue
        for alt, state in self.groups.items():
            if state.live < self._group_slots(state):
                return alt
        return None

    def _admit(self, now: float, max_wait_s: float) -> int:
        free = self._free_slots()
        if free <= 0 or not len(self.composer):
            return 0
        composed = self.composer.compose(limit=free, now=now,
                                         max_wait_s=max_wait_s)
        if composed is None:
            return 0
        admitted = 0
        unplaced = []
        pending_cows: Dict[int, List] = {g: [] for g in self.groups}
        for item in composed.items:
            g = self._route_admission(item)
            if g is None or not self._admit_one(item.payload, g,
                                                self.groups[g], now,
                                                pending_cows[g]):
                unplaced.append(item)
                continue
            admitted += 1
        # flush the wave's deferred divergence COWs: admissions sharing a
        # template coalesce their single-block copies into one batched
        # scatter per group (arena.cow_blocks) instead of one jit dispatch
        # per admission
        for g, pairs in pending_cows.items():
            if pairs:
                arena = self.groups[g].arena
                copied = arena.cow_blocks(pairs)
                self.admission_copy_bytes += (copied * arena.block_size
                                              * arena.token_bytes)
        for item in reversed(unplaced):   # push_front in reverse keeps FIFO
            self.composer.push_front(item)
        self.admission.note_admit(admitted)
        return admitted

    # -- chunked piggybacked prefill (paged arena only) -----------------
    def _build_chunk_fn(self, arena: KVArena, T: int, with_emb: bool,
                        api: Optional[ModelApi] = None,
                        cfg: Optional[ModelConfig] = None,
                        native: Optional[bool] = None,
                        counter: str = "prefill_traces"):
        """One jitted chunk step per (bucket, first-chunk) shape.

        Paged-NATIVE (attention families): run ``prefill_chunk_paged``
        straight against the page pools — chunk K/V rows scatter in place
        through the slot's block-table row, no dense view is gathered or
        re-scattered.  Fallback (pure-SSM, ring layouts, or the forced
        oracle): gather the slot's dense view, run ``prefill_chunk``, and
        scatter the written rows back via the multi-token
        ``append_rows``.

        ``api``/``cfg``/``native``/``counter`` default to the TARGET
        model; the speculative path passes the DRAFT model's to build its
        catch-up chunk step over the draft arena (compiles counted under
        ``draft_prefill_traces`` so the target's one-trace assertions stay
        meaningful)."""
        api = self.api if api is None else api
        cfg = self.cfg if cfg is None else cfg
        impl = self._impl
        # cache rows one call writes: the text bucket, plus the VLM image
        # prefix that rides along with the first chunk
        n_rows = T + (cfg.prefix_len
                      if with_emb and cfg.family == "vlm" else 0)

        if native is None:
            native = self.paged_native       # static: picked at trace time

        def _chunk(params, tokens, emb, pages, state, lens, slot, bt_row,
                   n_valid):
            setattr(self, counter,           # runs at trace time only
                    getattr(self, counter) + 1)
            start = lens[slot]
            # a FIRST chunk (start == 0, set by reset_len at admission)
            # must see freshly initialized per-slot state, not the slot's
            # previous tenant's conv/SSD/cross leftovers
            slot_state = [jnp.where(start > 0, s[:, slot],
                                    jnp.zeros_like(s[:, slot]))[:, None]
                          for s in state]
            batch = {"tokens": tokens}
            if emb is not None:
                batch["embeddings"] = emb
            if native:
                cache = arena.assemble(pages, slot_state, start[None])
                logits, new_cache = api.prefill_chunk_paged(
                    params, cfg, batch, cache, bt_row[None],
                    chunk_len=n_valid, block_size=arena.block_size,
                    impl=impl)
                new_pages, new_state = arena.disassemble(new_cache)
                new_len = jnp.asarray(kvcache.lens(new_cache),
                                      jnp.int32).reshape(-1)[0]
            else:
                dense = arena.dense_view(pages, bt_row[None])
                cache = arena.assemble(dense, slot_state, start[None])
                logits, new_cache = api.prefill_chunk(params, cfg, batch,
                                                      cache,
                                                      chunk_len=n_valid,
                                                      impl=impl)
                new_dense, new_state = arena.disassemble(new_cache)
                new_len = jnp.asarray(kvcache.lens(new_cache),
                                      jnp.int32).reshape(-1)[0]
                new_pages = arena.append_rows(
                    pages, new_dense, start[None], jnp.ones((1,), bool),
                    bt_row[None], n_tokens=n_rows,
                    valid_tokens=(new_len - start)[None])
            state = [s.at[:, slot].set(ns[:, 0].astype(s.dtype))
                     for s, ns in zip(state, new_state)]
            return logits, new_pages, state, lens.at[slot].set(new_len)

        return self._arena_jit(_chunk, arena, (3, 4, 5))

    def _run_chunk(self, arena: KVArena, s: _Slot, T: int) -> Any:
        """Advance one slot's prefill by one ``T``-bucket chunk; returns
        the chunk's logits (only the final chunk's are consumed)."""
        rem = len(s.req.tokens) - s.consumed
        n_valid = min(rem, T)
        toks = np.zeros((1, T), np.int32)
        toks[0, :n_valid] = s.req.tokens[s.consumed:s.consumed + n_valid]
        with_emb = (s.consumed == 0
                    and self.cfg.family in ("audio", "vlm"))
        emb = None
        if with_emb:
            emb = jnp.asarray(np.asarray(s.req.extras["embeddings"])[None])
        fn = self._chunk_fns.get((T, with_emb))
        if fn is None:
            fn = self._build_chunk_fn(arena, T, with_emb)
            self._chunk_fns[(T, with_emb)] = fn
        # copy-on-write before the chunk lands: a prefix-cache hit into a
        # PARTIAL block shares it read-only; our first write past the
        # divergence point forks a private copy (other slots and the
        # frozen index entry keep reading the original)
        copied = arena.ensure_writable(s.slot_id, s.consumed, n_valid)
        if copied:
            self.admission_copy_bytes += (copied * arena.block_size
                                          * arena.token_bytes)
        logits, arena.pages, arena.state, arena.lens = fn(
            self.params, jnp.asarray(toks), emb, arena.pages, arena.state,
            arena.lens, jnp.asarray(s.slot_id, jnp.int32),
            jnp.asarray(arena._block_tables[s.slot_id], jnp.int32),
            jnp.asarray(n_valid, jnp.int32))
        s.consumed += n_valid
        self.prefill_chunk_calls += 1
        self.prefill_tokens_computed += n_valid
        rows = n_valid + (self.cfg.prefix_len
                          if with_emb and self.cfg.family == "vlm" else 0)
        # chunk writes are APPENDS of fresh rows, not admission copies:
        # account them separately so the zero-copy admission gate
        # (admission_copy_bytes) measures actual copies only
        self.chunk_write_bytes += arena.chunk_bytes(rows)
        return logits, n_valid, T

    def _prefill_chunks(self, state: _GroupState) -> int:
        """(b2) Advance in-progress prefills, at most ``prefill_chunk``
        tokens per group per step — the piggyback budget that bounds how
        long the step's fused decode can be delayed by prompt work.  The
        final chunk's logits seed the request's first sampled token."""
        if state.arena is None or not self.chunked_prefill:
            return 0
        tr = self.trace
        budget = self.prefill_chunk_tokens
        done_tokens = 0
        for s in state.slots:
            if budget <= 0:
                break
            while s.prefilling and budget > 0:
                T = self._pick_bucket(len(s.req.tokens) - s.consumed,
                                      budget)
                if T is None:        # budget can't afford another bucket
                    budget = 0
                    break
                t0 = time.perf_counter()
                ct0 = tr.clock() if tr.enabled else 0.0
                logits, n_valid, T = self._run_chunk(state.arena, s, T)
                budget -= T
                done_tokens += n_valid
                if tr.enabled:
                    tr.complete(self.obs_name, str(s.req.rid),
                                "prefill_chunk", ct0, tokens=n_valid,
                                bucket=T, start=s.consumed - n_valid)
                if s.consumed >= len(s.req.tokens):
                    first_dev = self._sample(
                        logits, [self._req_seed(s.req)], [s.sample_idx],
                        [0])
                    with self._wait("first_token", tokens=n_valid):
                        first = int(np.asarray(first_dev)[0])
                    t1 = time.perf_counter()
                    s.prefill_s += t1 - t0
                    s.begin_decode(first, t1)
                    self._enable_spec(state, s)
                    self._spawn_forks(state, s, logits, t1)
                    if tr.enabled:
                        tid = str(s.req.rid)
                        tr.end(self.obs_name, tid,
                               tokens_computed=s.consumed)
                        tr.instant(self.obs_name, tid, "first_token")
                        tr.begin(self.obs_name, tid, "decode")
                    if state.prefix is not None:
                        # every FULL prompt block is now written and
                        # frozen: index the chain (hits extend existing
                        # paths; duplicated content keeps the first
                        # copy).  The partial tail block is deliberately
                        # NOT indexed yet — generation still appends into
                        # it, so freezing it now would make the owner COW
                        # its own tail; eviction indexes it once final.
                        state.prefix.insert(
                            s.req.tokens,
                            state.arena._block_tables[s.slot_id],
                            include_partial=False)
                else:
                    with self._wait("chunk", tokens=n_valid):
                        jax.block_until_ready(logits)
                    s.prefill_s += time.perf_counter() - t0
        return done_tokens

    # -- speculative decoding: draft arena + fused verify ---------------
    def _spec_goal(self, s: _Slot) -> int:
        """Draft-cache rows a slot needs before it can run a spec round:
        the draft always lags the known tokens by exactly TWO rows, so
        every round's step 0 feeds ``known[-2]`` (catch-up, output
        discarded) and step 1 feeds ``emitted[-1]`` to propose the first
        draft — one uniform (k+1)-step round, no per-round shape
        variation, one compile."""
        return len(s.req.tokens) + len(s.emitted) - 2

    def _ensure_draft(self, state: _GroupState) -> KVArena:
        if state.draft is None:
            # slot ids pair up with the target arena's
            state.draft = self._new_draft_arena(state.arena.capacity)
        return state.draft

    def _enable_spec(self, state: _GroupState, s: _Slot) -> None:
        """Arm speculation for a slot that just finished prefill: claim
        the MATCHING slot id in the group's draft arena (the two block
        tables stay aligned) and start the draft's catch-up chase —
        ``_draft_chunks`` prefill-chunks the known tokens into the draft
        cache while the slot keeps decoding normally; rounds start once
        the chase reaches the lag-1 goal.  Alloc failure degrades to
        plain decode (counted, never fatal).  Forks never speculate —
        their divergence is the point, and greedy drafts would collapse
        them."""
        if self.speculate_k <= 0 or s.sample_idx != 0 or s.done:
            return
        draft = self._ensure_draft(state)
        # draft rows run k past the known tokens mid-round
        total = min(len(s.req.tokens) + s.req.max_new_tokens
                    + self.speculate_k, draft.slot_tokens)
        if not draft.can_alloc(total):
            self.spec_degraded += 1
            return
        draft.alloc(total, slot=s.slot_id)
        draft.reset_len(s.slot_id)
        s.spec = True
        s.draft_len = 0

    def _draft_chunks(self, state: _GroupState) -> int:
        """Chase each speculating slot's draft cache toward its lag-1
        goal, at most one chunk budget per group per step (the same
        head-of-line bound as target prefill).  The goal moves +1 per
        normal decode step while the chase runs; the smallest chunk
        bucket is a whole block, so the chase always gains ground."""
        if state.draft is None:
            return 0
        draft = state.draft
        budget = self.prefill_chunk_tokens
        done_tokens = 0
        for s in state.slots:
            if budget <= 0:
                break
            if not s.spec or s.prefilling or s.done:
                continue
            goal = self._spec_goal(s)
            while s.draft_len < goal and budget > 0:
                T = self._pick_bucket(goal - s.draft_len, budget)
                if T is None:
                    budget = 0
                    break
                n_valid = min(goal - s.draft_len, T)
                known = np.concatenate(
                    [np.asarray(s.req.tokens, np.int32),
                     np.asarray(s.emitted, np.int32)])
                toks = np.zeros((1, T), np.int32)
                toks[0, :n_valid] = known[s.draft_len:s.draft_len + n_valid]
                fn = self._draft_chunk_fns.get(T)
                if fn is None:
                    fn = self._build_chunk_fn(
                        draft, T, False, api=self.draft_api,
                        cfg=self.draft_cfg, native=True,
                        counter="draft_prefill_traces")
                    self._draft_chunk_fns[T] = fn
                _, draft.pages, draft.state, draft.lens = fn(
                    self.draft_params, jnp.asarray(toks), None,
                    draft.pages, draft.state, draft.lens,
                    jnp.asarray(s.slot_id, jnp.int32),
                    jnp.asarray(draft._block_tables[s.slot_id], jnp.int32),
                    jnp.asarray(n_valid, jnp.int32))
                s.draft_len += n_valid
                budget -= T
                done_tokens += n_valid
                self.draft_prefill_tokens += n_valid
        return done_tokens

    def _build_verify_fn(self, arena: KVArena) -> Callable:
        """The ONE fused verify launch: score T = k+1 fed tokens per
        speculating slot against the target's paged cache
        (``api.verify_step_paged`` through the chunk-attention kernels
        with per-slot chunk lengths — 0 rows for non-speculating slots),
        then accept/reject with ``speculative_verify`` and commit each
        slot's length by its emit count, all inside one jit.  Compiles
        exactly once per service (``verify_traces``)."""
        api, cfg, impl = self.api, self.cfg, self._impl
        T = self.speculate_k + 1

        def _verify(params, tokens, dlogits, dtoks, pages, state, lens,
                    spec, seeds, sids, offs, block_tables, occ):
            self.verify_traces += 1          # runs at trace time only
            chunk_len = jnp.where(spec, T, 0).astype(jnp.int32)
            cache = arena.assemble(pages, state, lens)
            logits, new_cache = api.verify_step_paged(
                params, cfg, {"tokens": tokens}, cache, block_tables,
                chunk_len=chunk_len, block_size=arena.block_size,
                impl=impl)
            new_pages, new_state = arena.disassemble(new_cache)
            state2 = arena.merge_state(state, new_state, spec)
            out, n_emit = speculative_verify(
                logits, dlogits, dtoks, self._key, seeds, sids, offs,
                self.sampler, live=spec, occupancy=occ)
            new_lens = jnp.where(spec, lens + n_emit, lens)
            return out, n_emit, new_pages, state2, new_lens

        return self._arena_jit(_verify, arena, (4, 5, 6), n_lead=2)

    def _spec_round(self, state: _GroupState,
                    spec_slots: List[_Slot]) -> None:
        """One draft/verify round for every slot whose draft cache is
        caught up: k+1 fused DRAFT decode steps (step 0 replays
        ``known[-2]`` to close the lag, steps 1..k propose drafts from
        the STREAM_DRAFT counter streams), then ONE fused target verify
        launch commits up to k+1 tokens per slot.  After the round the
        draft rolls back to the new lag-1 goal (rejected proposals'
        rows become garbage past ``len``, overwritten by the next
        round)."""
        arena, draft = state.arena, state.draft
        cap = arena.capacity
        k = self.speculate_k
        tr = self.trace
        rt0 = tr.clock() if tr.enabled else 0.0
        live = np.zeros((cap,), bool)
        seeds = np.zeros((cap,), np.uint32)
        sids = np.zeros((cap,), np.uint32)
        offs = np.zeros((cap,), np.uint32)
        for s in spec_slots:
            sid = s.slot_id
            live[sid] = True
            seeds[sid] = np.uint32(self._req_seed(s.req) & 0xFFFFFFFF)
            sids[sid] = s.sample_idx
            offs[sid] = len(s.emitted)
        live_dev = jnp.asarray(live)
        if self._draft_decode_fn is None:
            self._draft_decode_fn = self._arena_jit(
                self._paged_decode_pure(draft, api=self.draft_api,
                                        cfg=self.draft_cfg, native=True,
                                        counter="draft_decode_traces"),
                draft, (2, 3, 4))
        drafts_host: List[np.ndarray] = []
        dlogit_steps: List[Any] = []
        for j in range(k + 1):
            tokens = np.zeros((cap,), np.int32)
            for s in spec_slots:
                if j == 0:
                    # catch-up row: the second-to-last known token (its
                    # output re-predicts a token we already have)
                    known_tail = (s.emitted[-2] if len(s.emitted) >= 2
                                  else s.req.tokens[-1])
                    tokens[s.slot_id] = known_tail
                elif j == 1:
                    tokens[s.slot_id] = s.emitted[-1]
                else:
                    tokens[s.slot_id] = drafts_host[j - 2][s.slot_id]
            logits, draft.pages, draft.state, draft.lens = \
                self._draft_decode_fn(
                    self.draft_params, jnp.asarray(tokens), draft.pages,
                    draft.state, draft.lens, live_dev,
                    draft.device_block_tables())
            self.draft_steps += 1
            if j >= 1:
                dlogit_steps.append(logits)
                d = self._sample(logits, seeds, sids, offs + (j - 1),
                                 live=live_dev, stream=STREAM_DRAFT)
                with self._wait("draft", live=len(spec_slots)):
                    drafts_host.append(np.asarray(d))
        dlogits = jnp.stack(dlogit_steps, axis=1)          # (cap, k, V)
        dtoks = np.stack(drafts_host, axis=1).astype(np.int32)
        vtok = np.zeros((cap, k + 1), np.int32)
        for s in spec_slots:
            sid = s.slot_id
            vtok[sid, 0] = s.emitted[-1]
            vtok[sid, 1:] = dtoks[sid]
            # COW guard over the whole verify span (prefix-frozen tails,
            # fork-shared prompt blocks)
            start = len(s.req.tokens) + len(s.emitted) - 1
            copied = arena.ensure_writable(sid, start, k + 1)
            if copied:
                self.admission_copy_bytes += (copied * arena.block_size
                                              * arena.token_bytes)
        if self._verify_fn is None:
            self._verify_fn = self._build_verify_fn(arena)
        with self._phase("verify", slots=len(spec_slots), k=k):
            out, n_emit, arena.pages, arena.state, arena.lens = \
                self._verify_fn(
                    self.params, jnp.asarray(vtok), dlogits,
                    jnp.asarray(dtoks), arena.pages, arena.state,
                    arena.lens, live_dev, jnp.asarray(seeds),
                    jnp.asarray(sids), jnp.asarray(offs),
                    arena.device_block_tables(), arena.device_occupancy())
            self.verify_launches += 1
        with self._wait("verify", live=len(spec_slots)):
            out_h, nem = np.asarray(out), np.asarray(n_emit)
        for s in spec_slots:
            sid = s.slot_id
            n = int(nem[sid])
            s.steps += 1
            for t in out_h[sid, :n]:
                # count only tokens the request actually keeps: verify can
                # commit past max_new/EOS, but those rows are garbage the
                # eviction discards, not accepted throughput
                self.accepted_tokens += 1
                s.push(int(t))
                if s.done:
                    break
            # roll the draft back to the NEW lag-1 goal: everything past
            # it is a rejected proposal's row (or the accepted ones we'll
            # re-feed), garbage past len by construction
            dl = self._spec_goal(s)
            draft.set_len(sid, dl)
            s.draft_len = dl
            if tr.enabled:
                tr.complete(self.obs_name, str(s.req.rid), "spec_round",
                            rt0, k=k, accepted=n)

    # -- n>1 parallel sampling: refcounted prompt-block forks -----------
    def _spawn_forks(self, state: _GroupState, s: _Slot, logits,
                     wall: float) -> None:
        """Fork ``n_samples - 1`` sibling slots off a primary that just
        finished prefill: each fork allocs with ``shared=`` the primary's
        prompt blocks (refcount bumps, ZERO prefill compute or copies),
        draws its own first token from the same final-chunk logits on its
        own ``sample_idx`` counter stream, and diverges from the shared
        tail block by copy-on-write on its first append.  Slot or block
        pressure spawns fewer than asked (counted as shortfall) — the
        primary always runs."""
        if s.sample_idx != 0:
            return
        asked = int(getattr(s.req, "n_samples", 1)) - 1
        want = min(asked + 1, self.n_samples_cap) - 1
        if want <= 0:
            # shortfall counts every sibling the caller asked for but the
            # category cap / batch budget denied, not just alloc failures
            self.fork_shortfall += max(0, asked)
            return
        arena = state.arena
        P = len(s.req.tokens) + self._extra_cache_tokens()
        total = P + s.req.max_new_tokens
        shared = list(arena._block_tables[s.slot_id][:arena.blocks_for(P)])
        seed = self._req_seed(s.req)
        first_dev = self._sample(
            jnp.broadcast_to(logits.reshape(1, -1),
                             (want, logits.shape[-1])),
            [seed] * want, list(range(1, want + 1)), [0] * want)
        with self._wait("fork", live=want):
            first = np.asarray(first_dev)
        spawned = 0
        for i in range(want):
            if (state.live >= self._group_slots(state)
                    or not arena.can_alloc(total, shared=shared)):
                break
            sid = arena.alloc(total, shared=shared)
            arena.set_len(sid, P)
            fork = _Slot(s.req, None, prefill_s=s.prefill_s,
                         admit_wall=s.admit_wall,
                         admitted_s=s.admitted_s, slot_id=sid,
                         submit_wall=s.submit_wall)
            fork.consumed = len(s.req.tokens)
            fork.sample_idx = i + 1
            fork.begin_decode(int(first[i]), wall)
            state.slots.append(fork)
            spawned += 1
            if self.trace.enabled:
                # forks live on their own "rid.sample" lane carrying only
                # a decode span: zero prefill is the point
                ftid = self._slot_tid(fork)
                self.trace.begin(self.obs_name, ftid, "decode", fork=True)
                self.trace.instant(self.obs_name, ftid, "first_token")
        self.forks_spawned += spawned
        self.fork_shortfall += asked - spawned
        if spawned:
            self._sibling_refs[s.req.rid] = spawned + 1

    # -- fused decode: paged arena path ---------------------------------
    def _paged_decode_pure(self, arena: KVArena,
                           api: Optional[ModelApi] = None,
                           cfg: Optional[ModelConfig] = None,
                           native: Optional[bool] = None,
                           counter: str = "decode_traces") -> Callable:
        """The fused decode step as a PURE function of
        ``(params, tokens, pages, state, lens, live, block_tables)`` ->
        ``(logits, pages, state, lens)`` — what ``_build_paged_decode_fn``
        jits, on one device or under the service mesh (MP-sharded paged
        decode).

        ``api``/``cfg``/``native``/``counter`` default to the TARGET
        model; the speculative path passes the DRAFT model's to build the
        fused draft step over the draft arena (compiles counted under
        ``draft_decode_traces``)."""
        api = self.api if api is None else api
        cfg = self.cfg if cfg is None else cfg
        impl = self._impl
        if native is None:
            native = self.paged_native       # static: picked at trace time

        def _step(params, tokens, pages, state, lens, live, block_tables):
            setattr(self, counter,           # runs at trace time only
                    getattr(self, counter) + 1)
            if native:
                # paged leaves stay PAGE POOLS: the family's attention
                # streams K/V through the block table in place and writes
                # only each live slot's new row — no dense view, no
                # re-scatter
                cache = arena.assemble(pages, state, lens)
                logits, new_cache = api.decode_step_paged(
                    params, cfg, tokens, cache, block_tables, live,
                    block_size=arena.block_size, impl=impl)
                new_pages, new_state = arena.disassemble(new_cache)
            else:
                dense = arena.dense_view(pages, block_tables)
                cache = arena.assemble(dense, state, lens)
                logits, new_cache = api.decode_step(params, cfg, tokens,
                                                    cache, impl=impl)
                new_dense, new_state = arena.disassemble(new_cache)
                new_pages = arena.append_rows(pages, new_dense, lens, live,
                                              block_tables)
            state = arena.merge_state(state, new_state, live)
            lens = jnp.where(live, lens + 1, lens)
            return logits, new_pages, state, lens

        return _step

    def _build_paged_decode_fn(self, arena: KVArena):
        # donate the arena buffers (args 2..4) so XLA appends in place
        # instead of re-materializing the page pool every decode step
        return self._arena_jit(self._paged_decode_pure(arena), arena,
                               (2, 3, 4))

    def _arena_jit(self, fn: Callable, arena: KVArena,
                   donate_argnums: Tuple[int, ...], n_lead: int = 1):
        """jit one arena step whose outputs are ``(*lead, pages, state,
        lens)``, donating the arena buffers.  Under a service mesh the
        buffers come back with the placements they went in with (no
        reshard, and the next call hits the same compile), and the step
        is traced and run under the mesh so the Pallas kernels split
        their heads over its ``model`` axis (``kernels/ops.py``)."""
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=donate_argnums)
        return _MeshStep(jax.jit(
            fn, donate_argnums=donate_argnums,
            out_shardings=(None,) * n_lead + arena.shardings()), self.mesh)

    def decode_cost_analysis(self, group: int = 0) -> Dict[str, Any]:
        """XLA cost analysis of the compiled fused decode step at the
        group's CURRENT arena shapes — the zero-gather regression surface
        (``BENCH_decode.json`` and the HLO tests assert the paged-native
        step's bytes accessed beat the dense-gather oracle's).  Uses a
        throwaway lowering so the serving fast path's jit cache and the
        ``decode_traces`` compile counter stay untouched."""
        state = self.groups[group]
        arena = self._ensure_arena(state)
        traces0, ptraces0 = self.decode_traces, self.prefill_traces
        try:
            lowered = jax.jit(self._paged_decode_pure(arena)).lower(
                self.params, jnp.zeros((arena.capacity,), jnp.int32),
                arena.pages, arena.state, arena.lens,
                jnp.ones((arena.capacity,), bool),
                arena.device_block_tables())
            cost = lowered.compile().cost_analysis()
        finally:
            self.decode_traces, self.prefill_traces = traces0, ptraces0
        return dict(cost)

    def _decode_group_paged(self, state: _GroupState) -> None:
        arena = state.arena
        cap = arena.capacity
        k = self.speculate_k
        tokens = np.zeros((cap,), np.int32)
        live = np.zeros((cap,), bool)
        seeds = np.zeros((cap,), np.uint32)
        sids = np.zeros((cap,), np.uint32)
        offs = np.zeros((cap,), np.uint32)
        n_keys = 0          # keys the live slots attend, after the append
        spec_round: List[_Slot] = []
        for s in state.slots:
            if s.done or s.prefilling:
                continue
            if s.spec:
                if (len(s.req.tokens) + len(s.emitted) + k
                        > arena.slot_tokens):
                    # tail of generation: a full round would write past
                    # the slot's table width — finish with plain decode
                    # (greedy tokens are identical either way)
                    state.draft.free(s.slot_id)
                    s.spec = False
                    s.draft_len = 0
                    self.spec_degraded += 1
                elif s.draft_len >= self._spec_goal(s):
                    spec_round.append(s)
                    continue
                # else: draft still chasing — decode normally this step
            sid = s.slot_id
            tokens[sid] = s.emitted[-1]
            live[sid] = True
            seeds[sid] = np.uint32(self._req_seed(s.req) & 0xFFFFFFFF)
            sids[sid] = s.sample_idx
            offs[sid] = len(s.emitted)
            # the append position can sit inside a block the prefix index
            # froze (this slot's own registered partial tail, a
            # block-aligned shared prefix whose last block the generation
            # now extends) or one an n>1 sibling still shares: COW first.
            # The arena's cheap guard makes this free when nothing in the
            # pool is shared, so the call is unconditional.
            pos = (len(s.req.tokens) + self._extra_cache_tokens()
                   + len(s.emitted) - 1)
            n_keys += pos + 1
            copied = arena.ensure_writable(sid, pos, 1)
            if copied:
                self.admission_copy_bytes += (
                    copied * arena.block_size * arena.token_bytes)
        if live.any():
            if self._paged_decode_fn is None:
                self._paged_decode_fn = self._build_paged_decode_fn(arena)
            live_dev = jnp.asarray(live)
            logits, arena.pages, arena.state, arena.lens = \
                self._paged_decode_fn(
                    self.params, jnp.asarray(tokens), arena.pages,
                    arena.state, arena.lens, live_dev,
                    arena.device_block_tables())
            n_live = int(live.sum())
            with self._phase("sample", live=n_live, keys=n_keys):
                toks_dev = self._sample(
                    logits, seeds, sids, offs, live=live_dev,
                    occupancy=arena.device_occupancy())
                with self._wait("decode", live=n_live):
                    toks = np.asarray(toks_dev)
            self.decode_steps += 1
            for slot in state.slots:
                if slot.done or slot.prefilling or not live[slot.slot_id]:
                    continue
                slot.steps += 1
                slot.push(int(toks[slot.slot_id]))
        if spec_round:
            self._spec_round(state, spec_round)

    # -- fused decode: dense (merge/select) path ------------------------
    def _decode_group_dense(self, state: _GroupState) -> None:
        live = np.array([not s.done for s in state.slots])
        if not live.any():
            return               # everything awaits eviction
        cur = jnp.asarray([s.emitted[-1] if not s.done else 0
                           for s in state.slots], jnp.int32)
        logits, state.cache = self.decode_fn(self.params, cur, state.cache)
        toks_dev = self._sample(
            logits, [self._req_seed(s.req) for s in state.slots],
            [s.sample_idx for s in state.slots],
            [len(s.emitted) for s in state.slots],
            live=jnp.asarray(live))
        with self._wait("decode", live=int(live.sum())):
            toks = np.asarray(toks_dev)
        self.decode_steps += 1
        for i, slot in enumerate(state.slots):
            if slot.done:
                continue
            slot.steps += 1
            slot.push(int(toks[i]))

    def _decode_group(self, state: _GroupState) -> None:
        """(c) One fused decode step over every occupied slot."""
        if not state.slots:
            return
        if state.arena is not None:
            self._decode_group_paged(state)
        else:
            self._decode_group_dense(state)

    # -- prefix-cache telemetry (summed across DP groups) ---------------
    def _prefix_totals(self):
        lk = ht = hits = ev = cow = 0
        for g in self.groups.values():
            if g.prefix is not None:
                lk += g.prefix.lookups
                hits += g.prefix.hits
                ht += g.prefix.hit_tokens
            if g.arena is not None:
                ev += g.arena.cached_evictions
                cow += g.arena.cow_copies
        return lk, hits, ht, ev, cow

    @property
    def prefix_hit_tokens(self) -> int:
        return self._prefix_totals()[2]

    @property
    def prefix_hits(self) -> int:
        return self._prefix_totals()[1]

    @property
    def prefix_evictions(self) -> int:
        return self._prefix_totals()[3]

    @property
    def prefix_cow_copies(self) -> int:
        return self._prefix_totals()[4]

    def _phase(self, name: str, **args):
        """One engine-phase span (``tid="engine"``) for ``with``."""
        return self.trace.span(self.obs_name, "engine", name, **args)

    def _wait(self, name: str, **args):
        """A span around a call where the host blocks on the device
        (``tid="wait"``): a round's ``step`` less its waits is host work.
        Asynchronous launches before it (admission scatters, copy-on-write
        copies) are paid inside the wait that follows them."""
        return self.trace.span(self.obs_name, "wait", name, **args)

    def _step_continuous(self, now: float, max_wait_s: float) -> StepStats:
        with self._phase("step") as step_span:
            copy0, whole0 = self.admission_copy_bytes, self.whole_cache_copies
            chunkw0 = self.chunk_write_bytes
            steps0, one0 = self.decode_steps, self.oneshot_prefills
            draft0, ver0 = self.draft_steps, self.verify_launches
            acc0, deg0 = self.accepted_tokens, self.spec_degraded
            fk0, fs0 = self.forks_spawned, self.fork_shortfall
            pfx0 = self._prefix_totals()
            moe0 = self._moe_stats.dropped if self._moe_stats else 0.0
            results: List[GenerationResult] = []
            with self._phase("evict") as sp:
                for group, state in self.groups.items():
                    results.extend(self._evict(group, state, now))
                sp.set(evicted=len(results))
            # admission control (inert under the "fifo" policy): learn the
            # caller's clock, shed with verdicts, order by slack, then park
            # a victim if the urgent head can't wait — all BEFORE compose
            # so the freed slot goes to the strictest deadline
            ctrl = self.admission
            rejected: List[AdmissionReject] = []
            preempt0, resume0 = ctrl.preemptions, ctrl.resumes
            if ctrl.active:
                with self._phase("preempt") as sp:
                    ctrl.note_step(now)
                    ctrl.order(now)      # slack order FIRST: shed walks it
                    rejected = self._shed_rejected(now)
                    self._maybe_preempt(now)
                    sp.set(shed=len(rejected),
                           parked=ctrl.preemptions - preempt0)
            with self._phase("admit") as sp:
                admitted = self._admit(now, max_wait_s)
                sp.set(admitted=admitted)
            chunk_tokens = 0
            for state in self.groups.values():
                with self._phase("chunk") as sp:
                    n = self._prefill_chunks(state)
                    chunk_tokens += n
                    self._draft_chunks(state)
                    sp.set(tokens=n)
                with self._phase("fused_decode"):
                    self._decode_group(state)
            pfx1 = self._prefix_totals()
            if self.trace.enabled:
                step_span.set(admitted=admitted, evicted=len(results),
                              in_flight=self.in_flight(),
                              pending=self.pending())
        verdict_count = lambda v: sum(1 for r in rejected
                                      if r.verdict is v)
        return StepStats(
            results=results, now=now, admitted=admitted,
            evicted=len(results), in_flight=self.in_flight(),
            pending=self.pending(),
            queue_time_s=self.queue_time_estimate(),
            admission_copy_bytes=self.admission_copy_bytes - copy0,
            chunk_write_bytes=self.chunk_write_bytes - chunkw0,
            whole_cache_copies=self.whole_cache_copies - whole0,
            decode_steps=self.decode_steps - steps0,
            prefill_chunk_tokens=chunk_tokens,
            oneshot_prefills=self.oneshot_prefills - one0,
            prefix_lookups=pfx1[0] - pfx0[0],
            prefix_hits=pfx1[1] - pfx0[1],
            prefix_hit_tokens=pfx1[2] - pfx0[2],
            prefix_evicted_blocks=pfx1[3] - pfx0[3],
            prefix_cow_blocks=pfx1[4] - pfx0[4],
            moe_dropped_tokens=((self._moe_stats.dropped - moe0)
                                if self._moe_stats else 0.0),
            rejected=rejected,
            deadline_missed=verdict_count(Outcome.DEADLINE_MISSED),
            congestion_rejects=verdict_count(Outcome.CONGESTION),
            offload_verdicts=verdict_count(Outcome.OFFLOAD),
            failed_rejects=verdict_count(Outcome.FAILED),
            evacuated=self._take_evacuated(),
            preempted=ctrl.preemptions - preempt0,
            resumed=ctrl.resumes - resume0,
            parked=len(ctrl.parked),
            draft_steps=self.draft_steps - draft0,
            verify_launches=self.verify_launches - ver0,
            accepted_tokens=self.accepted_tokens - acc0,
            spec_slots=sum(1 for g in self.groups.values()
                           for s in g.slots if s.spec and not s.done),
            forks_spawned=self.forks_spawned - fk0,
            fork_shortfall=self.fork_shortfall - fs0,
            spec_degraded=self.spec_degraded - deg0)

    # ------------------------------------------------------------------
    # sync mode: run-to-completion batches (the pre-slot baseline)
    # ------------------------------------------------------------------
    def run_batch(self, composed: ComposedBatch, *,
                  now: float = 0.0) -> List[GenerationResult]:
        reqs = [item.payload for item in composed.items]
        group = self.router.route(session=reqs[0].stream)
        toks, lens = self._pad_prompts(reqs)
        max_new = max(r.max_new_tokens for r in reqs)
        cache_size = int(toks.shape[1] + max_new)

        t0 = time.perf_counter()
        batch = self._build_batch(reqs, toks)
        logits, cache = self.prefill_fn(self.params, batch, cache_size)
        logits = jax.block_until_ready(logits)
        t1 = time.perf_counter()
        self.oneshot_prefills += len(reqs)
        self.prefill_tokens_computed += sum(len(r.tokens) for r in reqs)

        outs = []
        seeds = [self._req_seed(r) for r in reqs]
        zeros = [0] * len(reqs)
        cur = self._sample(logits, seeds, zeros, zeros)
        outs.append(np.asarray(cur))
        for i in range(max_new - 1):
            logits, cache = self.decode_fn(self.params, cur, cache)
            cur = self._sample(logits, seeds, zeros,
                               [i + 1] * len(reqs))
            outs.append(np.asarray(cur))
            self.decode_steps += 1
        jax.block_until_ready(cur)
        t2 = time.perf_counter()

        gen = np.stack(outs, axis=1)  # (B, max_new)
        results = []
        for i, r in enumerate(reqs):
            # sync mode charges the batch-wide decode time to every member
            # (the very distortion the slot path fixes)
            results.append(GenerationResult(
                rid=r.rid, tokens=gen[i, :r.max_new_tokens],
                prefill_s=t1 - t0, decode_s=t2 - t1, group=group,
                admitted_s=now, finished_s=now,
                decode_steps=max_new - 1))
            self._finish_request(r, group)
        return results

    def _step_sync(self, now: float, max_wait_s: float) -> StepStats:
        steps0 = self.decode_steps
        composed = self.composer.compose(now=now, max_wait_s=max_wait_s)
        results = ([] if composed is None
                   else self.run_batch(composed, now=now))
        return StepStats(results=results, now=now, admitted=len(results),
                         evicted=len(results), in_flight=self.in_flight(),
                         pending=self.pending(),
                         queue_time_s=self.queue_time_estimate(),
                         decode_steps=self.decode_steps - steps0)

    # ------------------------------------------------------------------
    def step(self, now: float = 0.0,
             max_wait_s: float = float("inf")) -> StepStats:
        """Advance the data plane by one scheduling round and report its
        telemetry.  Continuous mode: evict / admit / one fused decode
        step.  Sync mode: compose one batch (BS or MF semantics) and run
        it to completion."""
        stats = (self._step_sync(now, max_wait_s) if self.mode == "sync"
                 else self._step_continuous(now, max_wait_s))
        if self.metrics is not None:
            self.metrics.observe_step(self.obs_name, stats, runtime=self)
        return stats

    def drain(self, now: float = 0.0,
              max_wait_s: float = 0.0) -> List[GenerationResult]:
        """Step until queue and slots are empty; returns all results."""
        out: List[GenerationResult] = []
        while self.pending() or self.in_flight():
            before = (self.pending(), self.in_flight(), self.decode_steps,
                      self.prefill_chunk_calls, self.verify_launches,
                      self.draft_prefill_tokens)
            stats = self.step(now=now, max_wait_s=max_wait_s)
            out.extend(stats.results)
            if (self.pending(), self.in_flight(), self.decode_steps,
                    self.prefill_chunk_calls, self.verify_launches,
                    self.draft_prefill_tokens) == before \
                    and not stats.results:
                break            # no progress possible (e.g. empty compose)
        return out


class EparaServingEngine:
    """Multi-service front door: submits requests to ServiceRuntimes by
    service name.  Placement/offload decisions come from the control plane
    (see examples/serve_cluster.py); this class is the data plane.  The
    per-service ``StepStats`` of the latest round are kept in
    ``last_stats`` for the handler's queue-time feedback."""

    def __init__(self):
        self.runtimes: Dict[str, ServiceRuntime] = {}
        self.last_stats: Dict[str, StepStats] = {}
        self._results: List[GenerationResult] = []

    def deploy(self, name: str, runtime: ServiceRuntime) -> None:
        if not runtime._obs_named:
            # observability labels follow the DEPLOYED name (two services
            # can share a ModelConfig), unless the caller pinned one
            runtime.obs_name = name
        self.runtimes[name] = runtime

    def submit(self, service: str, req: GenerationRequest,
               now: float = 0.0) -> None:
        self.runtimes[service].submit(req, now)

    def step(self, now: float = 0.0,
             max_wait_s: float = 0.0) -> List[GenerationResult]:
        """One scheduling round across every deployed runtime."""
        out: List[GenerationResult] = []
        for name, rt in self.runtimes.items():
            stats = rt.step(now=now, max_wait_s=max_wait_s)
            self.last_stats[name] = stats
            out.extend(stats.results)
        self._results.extend(out)
        return out

    def drain(self, now: float = 0.0) -> List[GenerationResult]:
        return self.serve_until_idle(now=now)

    def serve_until_idle(self, now: float = 0.0, max_wait_s: float = 0.0,
                         on_stats: Optional[Callable] = None,
                         clock: Optional[Callable[[], float]] = None
                         ) -> List[GenerationResult]:
        """Step every runtime round-robin until no runtime can make
        progress, invoking ``on_stats(service, stats)`` after each round —
        the hook the launchers use to feed ``StepStats.queue_time_s`` back
        into the control plane's handler state.  ``clock`` (when given)
        supplies each round's ``now`` — a live clock is what makes the
        admission controller's deadlines bite (a frozen ``now`` never
        expires anything)."""
        out: List[GenerationResult] = []
        progress = True
        while progress:
            progress = False
            for name, rt in self.runtimes.items():
                if not (rt.pending() or rt.in_flight()):
                    continue
                stats = rt.step(now=clock() if clock is not None else now,
                                max_wait_s=max_wait_s)
                self.last_stats[name] = stats
                out.extend(stats.results)
                if on_stats is not None:
                    on_stats(name, stats)
                if (stats.results or stats.admitted or stats.decode_steps
                        or stats.prefill_chunk_tokens or stats.rejected
                        or stats.verify_launches or stats.draft_steps):
                    progress = True
        self._results.extend(out)
        return out
