"""End-to-end serving driver (the paper-kind driver, deliverable b):
deploy reduced variants of THREE assigned architectures (dense + SSM +
VLM) across a simulated edge cloud and serve a batched request stream
through the full EPARA control plane — allocator, SSSP placement, ring
sync, and per-request handler decisions, with MF batch composition for
the frequency service and sticky DP routing for the stateful SSM.

  PYTHONPATH=src python examples/serve_cluster.py [--requests 24]
"""
import argparse
import time

import numpy as np

from repro.configs import get_config, reduced
from repro.core import (EdgeCloudControlPlane, Outcome, Request, ServerSpec,
                        ServiceSpec, Sensitivity)
from repro.core.faults import FaultEvent, FaultInjector, FaultSpec
from repro.launch.serve import init_params
from repro.serving.engine import (EparaServingEngine, GenerationRequest,
                                  ServiceRuntime)
from repro.serving.failover import ClusterSupervisor, RetryPolicy

ARCHS = ["codeqwen1.5-7b", "mamba2-2.7b", "paligemma-3b"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=18)
    ap.add_argument("--servers", type=int, default=3)
    ap.add_argument("--chaos", action="store_true",
                    help="crash one server mid-burst (then restart it): "
                         "its queued/in-flight/parked requests evacuate "
                         "to survivors and every rid must still end "
                         "served-or-verdicted")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON of request lifecycles "
                         "and engine phases (Perfetto-loadable)")
    ap.add_argument("--metrics-out", default="",
                    help="write Prometheus text exposition (or a JSONL "
                         "snapshot when the path ends in .jsonl)")
    args = ap.parse_args()

    tracer = metrics = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer()
    if args.metrics_out:
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()

    specs, cfgs = {}, {}
    for a in ARCHS:
        full = get_config(a)
        freq = full.epara_sensitivity == "frequency"
        specs[a] = ServiceSpec(
            name=a, flops_per_request=2 * full.active_param_count() * 64,
            weights_bytes=full.param_count() * 2.0,
            vram_bytes=full.param_count() * 3.0,
            sensitivity=Sensitivity.FREQUENCY if freq
            else Sensitivity.LATENCY,
            slo_latency_s=2.0, slo_fps=20.0 if freq else 0.0,
            stateful=full.family in ("ssm", "hybrid"))
        cfgs[a] = reduced(full)

    servers = [ServerSpec(sid=i, num_gpus=4) for i in range(args.servers)]
    cp = EdgeCloudControlPlane(servers, specs)
    placements = cp.run_placement(
        {(a, s.sid): 5.0 for a in ARCHS for s in servers})
    print("plans:")
    for a, plan in cp.plans.items():
        print(f"  {a:18s} {plan.category} mp={plan.mp} bs={plan.bs} "
              f"mt={plan.mt} mf={plan.mf} dp={plan.dp} "
              f"sticky={plan.sticky}")
    print("placements:", placements)

    engines = {s.sid: EparaServingEngine() for s in servers}
    rng = np.random.default_rng(0)
    for svc, sid in placements:
        if sid < 0:
            continue
        cfg = cfgs[svc]
        params = init_params(cfg, seed=0)
        engines[sid].deploy(svc, ServiceRuntime(cfg, params, cp.plans[svc],
                                                tracer=tracer,
                                                metrics=metrics))

    cp.publish_all(0.0)
    for _ in range(args.servers):
        cp.sync_step(0.0)

    t0 = time.time()
    injector = None
    if args.chaos:
        # deterministic mid-burst crash of one service host, restarted a
        # few rounds later (rejoins via repair + re-publish); the first
        # logical round is t=1.0, so at_s=2.0 lands while requests are
        # still queued or decoding
        victim = next(sid for sid, e in engines.items() if e.runtimes)
        injector = FaultInjector(FaultSpec(events=(
            FaultEvent(at_s=2.0, kind="crash", sid=victim),
            FaultEvent(at_s=6.0, kind="restart", sid=victim))))
        print(f"chaos: crash server {victim} at t=2, restart at t=6")
    supervisor = ClusterSupervisor(cp, engines,
                                   retry=RetryPolicy(base_timeout_s=4.0),
                                   injector=injector, metrics=metrics,
                                   tracer=tracer)
    for i in range(args.requests):
        svc = ARCHS[i % len(ARCHS)]
        cfg = cfgs[svc]
        at = int(rng.integers(0, args.servers))
        extras = None
        if cfg.family == "vlm":
            extras = {"embeddings": np.zeros((cfg.prefix_len, cfg.d_model),
                                             np.float32)}
        supervisor.submit(svc, GenerationRequest(
            rid=i, tokens=rng.integers(0, cfg.vocab_size, 8,
                                       dtype=np.int64).astype(np.int32),
            max_new_tokens=6, stream=i % 4, extras=extras),
            at_server=at, now=0.0)
    # the supervisor steps every runtime until each rid is served or
    # verdicted, feeding queue-time estimates back to the handler state
    # and recovering from any injected faults along the way
    report = supervisor.run_until_idle()
    results = report.results
    outcomes = report.outcomes
    dt = time.time() - t0
    toks = sum(len(r.tokens) for r in results)
    steps = sum(rt.decode_steps for eng in engines.values()
                for rt in eng.runtimes.values())
    traces = sum(rt.decode_traces for eng in engines.values()
                 for rt in eng.runtimes.values())
    copies = sum(rt.whole_cache_copies for eng in engines.values()
                 for rt in eng.runtimes.values())
    chunks = sum(rt.prefill_chunk_calls for eng in engines.values()
                 for rt in eng.runtimes.values())
    deployed = sum(len(eng.runtimes) for eng in engines.values())
    print(f"\nserved {len(results)}/{args.requests} requests "
          f"({toks} tokens, {steps} fused decode steps, {chunks} prefill "
          f"chunks) in {dt:.1f}s — handler outcomes: {outcomes}")
    print(f"paged arena: {traces} decode compiles across {deployed} "
          f"deployed runtimes, {copies} whole-cache admission copies")
    if args.chaos:
        print(f"chaos: {report.evacuated} evacuated, {report.failovers} "
              f"failovers, {report.duplicates} duplicates deduplicated, "
              f"{len(report.rejects)} verdicted, "
              f"accounted {report.accounted}/{args.requests}")
    if tracer is not None:
        tracer.export(args.trace_out)
        print(f"trace: {tracer.emitted} events -> {args.trace_out}")
    if metrics is not None:
        if args.metrics_out.endswith(".jsonl"):
            metrics.append_jsonl(args.metrics_out)
        else:
            metrics.write_prometheus(args.metrics_out)
        print(f"metrics -> {args.metrics_out}")
    # served-or-verdicted: every rid is accounted for even when a server
    # crashed mid-burst (chaos mode); without faults nothing may be
    # verdicted at all
    assert report.accounted == args.requests, \
        (report.accounted, args.requests)
    if not args.chaos:
        assert len({r.rid for r in results}) == args.requests
    assert copies == 0          # arena admissions never copy the live batch


if __name__ == "__main__":
    main()
