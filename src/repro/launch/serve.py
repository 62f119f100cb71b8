"""Serving launcher: deploy services through the EPARA control plane and
drive batched requests end-to-end (the paper-kind driver).

Each "edge server" is a ServiceRuntime deployment; the EPARA allocator
picks (MP, BS, MT, MF, DP) per service, the SSSP placement assigns services
to servers, and the distributed handler routes every request (local first,
then idle-goodput-weighted offload).  ``--size reduced`` (the default)
serves 2-layer, d_model-128 variants sized for CPU tests; ``--size full``
serves each config at its published widths.  Weights are random, drawn
from ``--seed`` and the arch id, so every process builds the same model.

  PYTHONPATH=src python -m repro.launch.serve --archs minicpm-2b,mamba2-2.7b \
      --servers 3 --requests 24
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
import zlib
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduced
from repro.kernels import ops
from repro.core import (EdgeCloudControlPlane, GPUSpec, Outcome, Request,
                        ServerSpec, ServiceSpec, Sensitivity, allocate)
from repro.core.faults import FaultInjector, FaultSpec, random_fault_spec
from repro.models.registry import model_api
from repro.serving.engine import (PREFIX_CACHEABLE_FAMILIES,
                                  EparaServingEngine, GenerationRequest,
                                  ServiceRuntime)
from repro.serving.failover import ClusterSupervisor, RetryPolicy


def service_spec_for(cfg) -> ServiceSpec:
    return ServiceSpec(
        name=cfg.name,
        flops_per_request=2.0 * cfg.active_param_count() * 64,
        weights_bytes=cfg.param_count() * 2.0,
        vram_bytes=cfg.param_count() * 2.0 * 1.5 + 5e8,
        sensitivity=Sensitivity(cfg.epara_sensitivity),
        slo_latency_s=2.0, slo_fps=20.0 if
        cfg.epara_sensitivity == "frequency" else 0.0,
        arch=cfg.name, stateful=cfg.family in ("ssm", "hybrid"),
        prefix_cacheable=cfg.family in PREFIX_CACHEABLE_FAMILIES)


CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def setup_compile_cache() -> Optional[str]:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads it itself), else in a
    fixed ``.jax_cache/`` at the checkout root — a fixed path, because the
    path is part of what a later process must match to hit.  The CPU
    backend gets no default cache: its compiles are quick, and XLA:CPU's
    cached code is tied to the CPU features of the host that built it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path is None and jax.default_backend() != "cpu":
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_params(cfg, seed: int, mesh=None):
    """Random weights from ``seed`` and the arch id (``crc32``, never the
    per-process salted ``hash``), initialised by one jitted program so the
    peak is the parameters themselves; under a mesh they are born with
    the serving shardings (``meshlib.param_specs``, pure tensor
    parallelism), so no device ever holds the whole model."""
    init = model_api(cfg).init
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(cfg.name.encode()))
    out_shardings = None
    if mesh is not None:
        from repro.launch import mesh as meshlib
        shapes = jax.eval_shape(lambda k: init(k, cfg), key)
        out_shardings = meshlib.named(
            mesh, meshlib.param_specs(mesh, shapes, fsdp=False))
    return jax.jit(lambda k: init(k, cfg), out_shardings=out_shardings)(key)


@dataclasses.dataclass
class ServeRun:
    """What one launcher run served, for callers that check it."""
    exit_code: int
    prompts: Dict[int, np.ndarray]        # rid -> prompt tokens
    results: List[Any]                    # GenerationResult per served rid
    cfgs: Dict[str, Any]                  # service -> served config
    params: Dict[str, Any]                # service -> weights
    slots: Dict[str, int]                 # service -> arena slots per group
    decode_traces: int
    serve_s: float                        # wall clock of the serving loop


def main(argv=None) -> int:
    return serve(argv).exit_code


def serve(argv=None) -> ServeRun:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default="minicpm-2b,mamba2-2.7b")
    ap.add_argument("--servers", type=int, default=3)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--prompt-len", default="6",
                    help="prompt tokens per request: N, or LO,HI for "
                         "lengths drawn uniformly from [LO, HI]")
    ap.add_argument("--size", choices=("reduced", "full"), default="reduced",
                    help="reduced = 2-layer d_model-128 variants (CPU "
                         "tests); full = the published config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", choices=("continuous", "sync"),
                    default="continuous",
                    help="serving data plane: slot-based continuous "
                         "batching (default) or run-to-completion batches")
    ap.add_argument("--kvcache-impl", choices=("paged", "dense"),
                    default="paged",
                    help="cache data plane: fixed-capacity paged KV arena "
                         "(default; one decode compile, zero-copy "
                         "admissions) or the legacy dense merge path")
    ap.add_argument("--max-seq-len", type=int, default=256,
                    help="per-slot token budget the paged arena is sized "
                         "for (prompt + max_new_tokens)")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="disable chunked piggybacked prefill (prompts "
                         "then prefill in one shot at admission, stalling "
                         "live decode slots and retracing per prompt "
                         "length)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunk bucket size in tokens (0 = the plan's "
                         "category-derived default: small for latency "
                         "services, large for frequency services)")
    ap.add_argument("--block-size", type=int, default=32,
                    help="paged-arena block size in tokens (the prefix "
                         "cache's sharing granularity)")
    ap.add_argument("--prefix-cache", type=int, default=-1,
                    help="radix prefix-cache retention: -1 = the plan's "
                         "category-derived bound (frequency retains "
                         "aggressively, latency bounded), 0 = disabled, "
                         ">0 = max idle cached blocks")
    ap.add_argument("--kv-dtype", default="auto",
                    help="paged-KV pool precision: 'auto' = the plan's "
                         "category-derived choice (frequency services "
                         "quantize blocks to int8 with per-row scales, "
                         "latency services keep the model dtype), or an "
                         "explicit 'bf16'/'int8' override for every "
                         "service")
    ap.add_argument("--admission-policy", choices=("fifo", "sdf"),
                    default="fifo",
                    help="admission control: arrival-order fifo (default) "
                         "or strictest-deadline-first — slack-ordered "
                         "queues, explicit reject verdicts, and preemption "
                         "of lazy decodes by block-table parking")
    ap.add_argument("--no-preempt", action="store_true",
                    help="with --admission-policy=sdf, disable block-table "
                         "parking (shed-only admission control)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request completion deadline in seconds from "
                         "submission (0 = none); with sdf admission, "
                         "requests that cannot make it are rejected with "
                         "a verdict instead of served dead")
    ap.add_argument("--speculate", type=int, default=-1,
                    help="speculative decoding draft depth k: -1 = the "
                         "plan's category-derived choice (latency "
                         "services speculate when a draft is given, "
                         "frequency services don't), 0 = disabled, >0 = "
                         "propose k tokens per fused verify launch "
                         "(requires --draft-arch)")
    ap.add_argument("--draft-arch", default="",
                    help="arch id of the small draft model that proposes "
                         "tokens for speculative decoding; must share "
                         "family and vocab with the target service "
                         "(incompatible services deploy non-speculative)")
    ap.add_argument("--n-samples", type=int, default=1,
                    help="parallel samples per request: n-1 sibling slots "
                         "fork off the prompt's blocks by refcount and "
                         "diverge copy-on-write (capped by the plan's "
                         "category-derived resolved_n_samples; >1 is "
                         "only diverse with a stochastic sampler)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace-event JSON of every "
                         "request's lifecycle spans and the engine's "
                         "per-step phases to this path (load in Perfetto "
                         "or chrome://tracing); default off — the tracer "
                         "is byte-inert either way")
    ap.add_argument("--metrics-out", default="",
                    help="write the metrics registry to this path at the "
                         "end of the run: Prometheus text exposition, or "
                         "a JSONL snapshot when the path ends in .jsonl")
    ap.add_argument("--calibrate-out", default="",
                    help="fold the run's measured telemetry (speculative "
                         "acceptance, prefix hit rates, prefill cost) "
                         "into SimConfig overrides and write the "
                         "calibration report JSON to this path")
    ap.add_argument("--fault-spec", default="",
                    help="replay a deterministic fault schedule from this "
                         "JSON file (core/faults.py FaultSpec) against "
                         "the run: crashes/restarts, stragglers, digest "
                         "corruption, dropped offload handoffs")
    ap.add_argument("--chaos-seed", type=int, default=-1,
                    help="generate a random (but seed-deterministic) "
                         "fault schedule instead of --fault-spec; -1 = "
                         "no injected faults (default)")
    ap.add_argument("--chaos-horizon-s", type=float, default=20.0,
                    help="time horizon the generated --chaos-seed "
                         "schedule spreads its events over (logical "
                         "rounds unless --admission-policy=sdf)")
    ap.add_argument("--retry-timeout-s", type=float, default=8.0,
                    help="base offload/handoff timeout before a request "
                         "retries on the next-best peer (exponential "
                         "backoff per attempt)")
    ap.add_argument("--retry-max-attempts", type=int, default=4,
                    help="placement attempts per request before a dead "
                         "avenue draws an explicit FAILED verdict")
    ap.add_argument("--pjit-decode", action="store_true",
                    help="build each service's fused paged decode step "
                         "under pjit on a (1, device_count) service mesh "
                         "(data, model) — the MP-sharded zero-gather "
                         "path; on one CPU device this is a trivial mesh "
                         "but exercises the same build")
    args = ap.parse_args(argv)

    # mirror the engine's knob validation at the flag boundary so a bad
    # value fails with a usage error instead of a deep ValueError
    if args.block_size < 1:
        ap.error(f"--block-size must be positive, got {args.block_size}")
    if args.prefill_chunk < 0 or (args.prefill_chunk
                                  and args.prefill_chunk % args.block_size):
        ap.error(f"--prefill-chunk must be 0 (category default) or a "
                 f"positive multiple of --block-size={args.block_size}, "
                 f"got {args.prefill_chunk}")
    if args.prefix_cache < -1:
        ap.error(f"--prefix-cache must be -1 (category default), 0 "
                 f"(disabled) or a positive block count, got "
                 f"{args.prefix_cache}")
    if args.kv_dtype not in ("auto", "bf16", "int8"):
        ap.error(f"--kv-dtype must be auto (category default), bf16 or "
                 f"int8, got {args.kv_dtype!r}")
    if args.kv_dtype == "int8" and args.kvcache_impl != "paged":
        ap.error("--kv-dtype=int8 requires --kvcache-impl=paged (only "
                 "page pools are block-quantized)")
    if args.admission_policy != "fifo" and args.mode != "continuous":
        ap.error("--admission-policy=sdf requires --mode=continuous (the "
                 "controller acts between composer and slot engine)")
    if args.deadline_s < 0:
        ap.error(f"--deadline-s must be >= 0, got {args.deadline_s}")
    if args.speculate < -1:
        ap.error(f"--speculate must be -1 (category default), 0 "
                 f"(disabled) or a positive draft depth, got "
                 f"{args.speculate}")
    if args.speculate > 0 and not args.draft_arch:
        ap.error("--speculate > 0 requires --draft-arch (the model that "
                 "proposes the k tokens)")
    if args.draft_arch and args.draft_arch not in ARCH_IDS:
        ap.error(f"unknown --draft-arch {args.draft_arch!r}")
    if args.draft_arch and (args.mode != "continuous"
                            or args.kvcache_impl != "paged"
                            or args.no_chunked_prefill):
        ap.error("--draft-arch requires --mode=continuous, "
                 "--kvcache-impl=paged and chunked prefill (the draft "
                 "cache is chased through the paged chunk path)")
    if args.n_samples < 1:
        ap.error(f"--n-samples must be >= 1, got {args.n_samples}")
    if args.fault_spec and args.chaos_seed >= 0:
        ap.error("--fault-spec and --chaos-seed are mutually exclusive "
                 "(a replayed schedule IS the seed's output)")
    if args.retry_timeout_s <= 0:
        ap.error(f"--retry-timeout-s must be positive, got "
                 f"{args.retry_timeout_s}")
    if args.retry_max_attempts < 1:
        ap.error(f"--retry-max-attempts must be >= 1, got "
                 f"{args.retry_max_attempts}")
    try:
        bounds = [int(x) for x in args.prompt_len.split(",")]
    except ValueError:
        bounds = []
    plo, phi = (bounds * 2)[:2] if len(bounds) in (1, 2) else (0, 0)
    if not 1 <= plo <= phi:
        ap.error(f"--prompt-len must be N or LO,HI with 1 <= LO <= HI, got "
                 f"{args.prompt_len!r}")
    kv_dtype = -1 if args.kv_dtype == "auto" else args.kv_dtype
    dev = jax.devices()[0]
    print(f"backend={jax.default_backend()} device_kind={dev.device_kind} "
          f"devices={jax.device_count()} impl={ops.default_impl()} "
          f"size={args.size}")

    arch_ids = [a.strip() for a in args.archs.split(",")]
    for a in arch_ids:
        assert a in ARCH_IDS, f"unknown arch {a}"

    # control plane: EPARA allocator + placement + handler
    servers = [ServerSpec(sid=i, num_gpus=4) for i in range(args.servers)]
    specs = {}
    cfgs = {}
    for a in arch_ids:
        full = get_config(a)
        specs[a] = service_spec_for(full)
        cfgs[a] = full if args.size == "full" else reduced(full)
    cp = EdgeCloudControlPlane(servers, specs)
    demand = {(a, s.sid): 4.0 for a in arch_ids for s in servers}
    placements = cp.run_placement(demand)
    print("EPARA plans:")
    for a, plan in cp.plans.items():
        kv = plan.resolved_kv_dtype() if kv_dtype == -1 else kv_dtype
        print(f"  {a:20s} {plan.category} mp={plan.mp} bs={plan.bs} "
              f"mt={plan.mt} mf={plan.mf} dp={plan.dp} kv={kv}")
    print(f"placements: {placements}")

    # data plane: one engine per server, reduced models
    engines = {s.sid: EparaServingEngine() for s in servers}
    # observability (repro/obs): one tracer + one registry shared by every
    # runtime — service names become trace processes / metric labels.
    # Default off; enabled it is still byte-inert (asserted by the tests)
    tracer = metrics = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer()
    if args.metrics_out:
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()
    rng = np.random.default_rng(args.seed)
    service_mesh = None
    if args.pjit_decode:
        # MP-sharded serving: weights, arena and every arena step live on
        # the (1, device_count) service mesh (heads over ``model``)
        from repro.launch import mesh as meshlib
        service_mesh = meshlib.make_mesh((1, jax.device_count()),
                                         ("data", "model"))
    draft_cfg = draft_params = None
    if args.draft_arch:
        draft_cfg = get_config(args.draft_arch)
        if args.size == "reduced":
            draft_cfg = reduced(draft_cfg)
        draft_params = init_params(draft_cfg, args.seed, service_mesh)
    weights = {a: init_params(cfgs[a], args.seed, service_mesh)
               for a in {svc for svc, sid in placements if sid >= 0}}
    for svc, sid in placements:
        if sid < 0:
            continue
        cfg = cfgs[svc]
        params = weights[svc]
        chunked = (None if not args.no_chunked_prefill else False)
        # the draft only pairs with same-family same-vocab attention
        # services; the rest deploy non-speculative (an explicit
        # --speculate > 0 still reaches the engine's loud gate)
        compat = (draft_cfg is not None
                  and cfg.family == draft_cfg.family
                  and cfg.vocab_size == draft_cfg.vocab_size
                  and cfg.family in PREFIX_CACHEABLE_FAMILIES)
        if draft_cfg is not None and not compat and args.speculate <= 0:
            print(f"  note: {svc} incompatible with draft "
                  f"{args.draft_arch} (family/vocab) — non-speculative")
        plan = dataclasses.replace(cp.plans[svc],
                                   prefix_cache=args.prefix_cache,
                                   kv_dtype=kv_dtype,
                                   admission=args.admission_policy,
                                   speculate=args.speculate)
        rt = ServiceRuntime(cfg, params, plan, mode=args.mode,
                            kvcache_impl=args.kvcache_impl,
                            max_seq_len=args.max_seq_len,
                            block_size=args.block_size,
                            chunked_prefill=chunked,
                            prefill_chunk=(args.prefill_chunk or None),
                            mesh=service_mesh,
                            preempt=not args.no_preempt,
                            draft_params=draft_params if compat else None,
                            draft_cfg=draft_cfg if compat else None,
                            tracer=tracer, metrics=metrics)
        engines[sid].deploy(svc, rt)

    # drive requests through handler -> engine, supervised: the
    # ClusterSupervisor owns the ledger (every rid ends served or
    # verdicted), the deadline-derived offload retry timeouts, and —
    # when a fault schedule is given — crash evacuation + failover
    cp.publish_all(0.0)
    for _ in range(len(servers)):
        cp.sync_step(0.0)
    # monotonic, not wall-clock: deadlines and throughput math must not
    # jump when NTP slews the system clock mid-run
    t0 = time.monotonic()
    # the data-plane clock: seconds since t0 — GenerationRequest deadlines
    # and the admission controller's slack estimates live in this frame
    deadline = args.deadline_s
    fault_spec = None
    if args.fault_spec:
        with open(args.fault_spec) as f:
            fault_spec = FaultSpec.from_json(f.read())
    elif args.chaos_seed >= 0:
        fault_spec = random_fault_spec(
            [s.sid for s in servers], args.chaos_horizon_s,
            seed=args.chaos_seed)
    if fault_spec is not None:
        print(f"fault schedule ({len(fault_spec.events)} events): "
              + ", ".join(f"{e.kind}@{e.at_s:.1f}s->s{e.sid}"
                          for e in fault_spec.events))
    supervisor = ClusterSupervisor(
        cp, engines,
        retry=RetryPolicy(base_timeout_s=args.retry_timeout_s,
                          max_attempts=args.retry_max_attempts),
        injector=FaultInjector(fault_spec) if fault_spec else None,
        metrics=metrics, tracer=tracer)
    prompts = {}
    for i in range(args.requests):
        svc = arch_ids[i % len(arch_ids)]
        at = int(rng.integers(0, len(servers)))
        cfg = cfgs[svc]
        n = plo if plo == phi else int(rng.integers(plo, phi + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
        prompts[i] = prompt
        extras = None
        if cfg.family in ("audio", "vlm"):
            dim = cfg.encoder_len if cfg.family == "audio" else cfg.prefix_len
            extras = {"embeddings": np.zeros((dim, cfg.d_model), np.float32)}
        supervisor.submit(svc, GenerationRequest(
            rid=i, tokens=prompt, max_new_tokens=args.max_new_tokens,
            stream=i, extras=extras, n_samples=args.n_samples,
            deadline_s=deadline if deadline else 0.0), at_server=at,
            now=0.0)
    clock = ((lambda: time.monotonic() - t0)
             if args.admission_policy == "sdf" else None)
    report = supervisor.run_until_idle(clock=clock)
    results = report.results
    outcomes = report.outcomes
    rts = [rt for eng in engines.values() for rt in eng.runtimes.values()]
    # the served tokens are on the host; also wait out the arenas' last
    # in-place updates so the clock covers all device work
    jax.block_until_ready([(g.arena.pages, g.arena.state, g.arena.lens)
                           for rt in rts for g in rt.groups.values()
                           if g.arena is not None])
    dt = time.monotonic() - t0
    toks = sum(len(r.tokens) for r in results)
    steps = sum(rt.decode_steps for eng in engines.values()
                for rt in eng.runtimes.values())
    traces = sum(rt.decode_traces for eng in engines.values()
                 for rt in eng.runtimes.values())
    copies = sum(rt.whole_cache_copies for eng in engines.values()
                 for rt in eng.runtimes.values())
    copy_mb = sum(rt.admission_copy_bytes for eng in engines.values()
                  for rt in eng.runtimes.values()) / 1e6
    print(f"served {len(results)}/{args.requests} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks/dt:.1f} tok/s, {steps} fused decode steps, "
          f"mode={args.mode}, kvcache={args.kvcache_impl})  "
          f"outcomes={outcomes}")
    slots = {}
    for eng in engines.values():
        for name, rt in eng.runtimes.items():
            caps = [g.arena.capacity for g in rt.groups.values()
                    if g.arena is not None]
            if caps:
                slots[name] = min(caps)
                print(f"arena: {name} {min(caps)} slots per group of plan "
                      f"bs={rt.plan.bs}, {rt.max_seq_len} tokens each, "
                      f"kv={rt.kv_dtype}")
    chunk_calls = sum(rt.prefill_chunk_calls for rt in rts)
    pf_traces = sum(rt.prefill_traces for rt in rts)
    chunk_mb = sum(rt.chunk_write_bytes for rt in rts) / 1e6
    native = sum(rt.paged_native for rt in rts)
    print(f"data plane: {traces} decode compiles, {pf_traces} prefill "
          f"compiles, {chunk_calls} prefill chunks, {copies} whole-cache "
          f"admission copies, {copy_mb:.2f} MB admission-copy bytes, "
          f"{chunk_mb:.2f} MB chunk writes, {native}/{len(rts)} "
          f"zero-gather paged-native services")
    hit_toks = sum(rt.prefix_hit_tokens for rt in rts)
    computed = sum(rt.prefill_tokens_computed for rt in rts)
    print(f"prefix cache: {sum(rt.prefix_hits for rt in rts)} hits, "
          f"{hit_toks} prompt tokens reused, {computed} computed, "
          f"{sum(rt.prefix_cow_copies for rt in rts)} COW copies, "
          f"{sum(rt.prefix_evictions for rt in rts)} LRU evictions, "
          f"{sum(rt.oneshot_prefills for rt in rts)} one-shot prefills")
    ver = sum(rt.verify_launches for rt in rts)
    acc = sum(rt.accepted_tokens for rt in rts)
    if ver or args.draft_arch:
        per = acc / ver if ver else 0.0
        print(f"speculative (draft={args.draft_arch or 'none'}): {ver} "
              f"verify launches, {acc} tokens accepted "
              f"({per:.2f}/launch), "
              f"{sum(rt.draft_steps for rt in rts)} draft steps, "
              f"{sum(rt.spec_degraded for rt in rts)} degraded, "
              f"{sum(rt.verify_traces for rt in rts)} verify compiles")
    forks = sum(rt.forks_spawned for rt in rts)
    if forks or args.n_samples > 1:
        print(f"parallel sampling (n={args.n_samples}): {forks} forks "
              f"spawned, {sum(rt.fork_shortfall for rt in rts)} shortfall")
    verdicts = {}
    for rt in rts:
        for v, n in rt.admission.verdicts.items():
            verdicts[v] = verdicts.get(v, 0) + n
    print(f"admission ({args.admission_policy}): {verdicts or 'no verdicts'}"
          f", {sum(rt.admission.preemptions for rt in rts)} preemptions, "
          f"{sum(rt.admission.resumes for rt in rts)} resumes, "
          f"{report.offload_retries} offload/timeout retries, "
          f"{len(report.rejects)} final rejects")
    if fault_spec is not None or report.failovers or report.duplicates:
        print(f"fault tolerance: {report.failovers} crash failovers, "
              f"{report.evacuated} requests evacuated, "
              f"{report.duplicates} duplicate completions deduplicated, "
              f"{report.dropped_offloads} handoffs dropped, "
              f"{report.heartbeat_misses} straggler rounds skipped, "
              f"{sum(rt.evacuations for rt in rts)} runtime evacuations")
    if tracer is not None:
        tracer.export(args.trace_out)
        print(f"trace: {tracer.emitted} events "
              f"({tracer.dropped} dropped by the ring) -> {args.trace_out}")
    if metrics is not None:
        if args.metrics_out.endswith(".jsonl"):
            metrics.append_jsonl(args.metrics_out)
        else:
            metrics.write_prometheus(args.metrics_out)
        print(f"metrics: {len(metrics._metrics)} series -> "
              f"{args.metrics_out}")
    if args.calibrate_out:
        from repro.obs import (merge_telemetry, telemetry_from_runtime,
                               write_calibration)
        tel = merge_telemetry(
            telemetry_from_runtime(name, rt)
            for eng in engines.values()
            for name, rt in eng.runtimes.items())
        cal = write_calibration(args.calibrate_out, tel)
        print(f"calibration: spec_accept_rate={cal.spec_accept_rate:.3f} "
              f"prefix_hit_rates={cal.prefix_hit_rates or {}} "
              f"prefill_token_s={cal.prefill_token_s:.2e} -> "
              f"{args.calibrate_out}")
    # every request is accounted for: served, or rejected with a verdict
    return ServeRun(
        exit_code=0 if report.accounted == args.requests else 1,
        prompts=prompts, results=results, cfgs=cfgs,
        params=weights, slots=slots, decode_traces=traces, serve_s=dt)


if __name__ == "__main__":
    raise SystemExit(main())
