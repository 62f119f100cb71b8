#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: the quickest proof that the
system still starts on the chip.  Not a benchmark.

One chip (no arguments): serves 16 greedy requests of minicpm-2b at its
published widths (40 layers, d_model 2304, 36 heads x 64, d_ff 5760,
vocab 122,753; random weights from a seed) through the normal path —
launcher, EPARA control plane, ``ServiceRuntime``, paged Pallas kernels —
with bf16 KV, and checks every served token against a plain f32 forward
of the same weights.  A second pass at the category's default KV
precision (int8) is reported only as its agreement with the first.

Four chips (``--chips 4``): serves codeqwen1.5-7b (about 16.4 GB of bf16
weights, more than one chip holds) tensor-parallel over a (1, 4)
(data, model) mesh, with the same check, and no other phase.

    python3 chip_smoke.py
    python3 chip_smoke.py --chips 4

Set ``JAX_COMPILATION_CACHE_DIR`` to place the compile cache; otherwise it
lives in ``.jax_cache/`` at the checkout root.  The last line of standard
output is one JSON object naming the device, printed only when every
phase passed; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# A served greedy token passes when the reference puts its logit within
# TOL_SIGMA standard deviations (of that position's reference logits) of
# the reference maximum.  Why that much: the served path rounds the
# residual stream to bf16 (2^-9 relative) at each of 2 x layers residual
# adds, keeps K/V and the logits in bf16, and so carries a hidden-state
# error of a few percent of its norm — about 0.03 sigma on each logit,
# and 0.04 sigma on the difference of two.  0.15 sigma is four of those;
# activations an order of magnitude coarser (8-bit floats) would miss it.
TOL_SIGMA = 0.15
REQUESTS, NEW_TOKENS, PROMPT_LENS, SEED = 16, 32, "100,500", 0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def reference_margins(run, svc: str):
    """Teacher-forced check of every served token: the plain forward
    (``impl="ref"``, f32 activations at ``highest`` matmul precision, the
    served bf16 weights) over prompt + served tokens, padded to one length
    so it compiles once (causal, so padding never reaches earlier rows).
    Returns each served token's reference-logit gap to the maximum, in
    units of that position's reference-logit standard deviation."""
    import jax
    import jax.numpy as jnp
    from repro.models.registry import model_api

    cfg = dataclasses.replace(run.cfgs[svc], dtype="float32")
    api = model_api(cfg)
    params = run.params[svc]

    @jax.jit
    def gaps(params, tokens, n_prompt, served):
        h, _ = api.forward_hidden(params, cfg, {"tokens": tokens}, impl="ref")
        logits = api.logits_fn(params, cfg, h)[0]          # (L, V) f32
        # row n_prompt - 1 + j predicts served token j
        rows = jax.lax.dynamic_slice_in_dim(logits, n_prompt - 1,
                                            served.shape[0])
        got = jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
        return (rows.max(axis=1) - got) / rows.std(axis=1)

    width = max(len(p) for p in run.prompts.values()) + NEW_TOKENS
    out = []
    with jax.default_matmul_precision("highest"):
        for res in run.results:
            prompt = run.prompts[res.rid]
            seq = np.zeros((1, width), np.int32)
            fed = np.concatenate([prompt, res.tokens[:-1]])
            seq[0, :len(fed)] = fed
            out.append(np.asarray(gaps(params, seq, len(prompt),
                                       np.asarray(res.tokens, np.int32))))
    return out


def serve_once(serve, compile_s, *flags):
    argv = ["--servers", "1", "--requests", str(REQUESTS),
            "--max-new-tokens", str(NEW_TOKENS), "--prompt-len", PROMPT_LENS,
            "--size", "full", "--seed", str(SEED),
            # one server: a retry timeout could only re-run a request on
            # the host already serving it
            "--retry-timeout-s", "1e9",
            "--max-seq-len", str(int(PROMPT_LENS.split(",")[1])
                                 + NEW_TOKENS), *flags]
    c0 = compile_s[0]
    run = serve.serve(argv)
    gc.collect()        # the runtimes hold reference cycles; free arenas
    served = len(run.results)
    print(f"requests served/submitted: {served}/{REQUESTS}")
    if run.exit_code != 0 or served != REQUESTS:
        raise SystemExit(f"served {served} of {REQUESTS} requests")
    print(f"decode compiles: {run.decode_traces}")
    if run.decode_traces != 1:
        raise SystemExit(f"{run.decode_traces} decode compiles, want 1")
    setup = compile_s[0] - c0
    toks = sum(len(r.tokens) for r in run.results)
    print(f"compile seconds (set-up): {setup:.3f}")
    print(f"smoke figure, not a benchmark: {toks} tokens in "
          f"{run.serve_s:.3f} s of serving loop less {setup:.3f} s compiling"
          f" = {toks / max(1e-9, run.serve_s - setup):.1f} tokens/s "
          f"(clock read after block_until_ready)")
    return run


def print_bytes_in_use(run, svc, devs):
    """After serving (arenas freed, weights kept for the check)."""
    import jax
    pbytes = sum(x.nbytes for x in jax.tree.leaves(run.params[svc]))
    for d in devs:
        used = d.memory_stats()["bytes_in_use"]
        print(f"device {d.id} bytes in use after serving: {used} "
              f"({used / pbytes:.4f} of the {pbytes} parameter bytes)")


def check(run, svc):
    gaps = reference_margins(run, svc)
    worst = max(float(g.max()) for g in gaps)
    bad = sum(int((g > TOL_SIGMA).sum()) for g in gaps)
    total = sum(g.size for g in gaps)
    print(f"reference check: {total - bad}/{total} served tokens within "
          f"{TOL_SIGMA} sigma of the f32 reference maximum "
          f"(worst gap {worst:.4f} sigma)")
    if bad:
        raise SystemExit(f"{bad} served tokens fail the reference check")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    from repro.kernels import ops
    from repro.launch import serve

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found {devs[0].platform}", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    print(f"compile cache: {serve.setup_compile_cache()}")
    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event in COMPILE_EVENTS:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    impl = ops.default_impl()
    print(f"impl: {impl}")
    if impl != "pallas":
        raise SystemExit(f"impl {impl!r} on a TPU, want 'pallas'")

    if args.chips == 4:
        svc = "codeqwen1.5-7b"
        run = serve_once(serve, compile_s, "--archs", svc, "--kv-dtype",
                         "bf16", "--pjit-decode")
        print(f"capacity: {run.slots[svc]} slots")
        print_bytes_in_use(run, svc, devs)
        check(run, svc)
    else:
        svc = "minicpm-2b"
        run = serve_once(serve, compile_s, "--archs", svc,
                         "--kv-dtype", "bf16")
        print(f"capacity: {run.slots[svc]} slots")
        print(f"peak_bytes_in_use: "
              f"{devs[0].memory_stats()['peak_bytes_in_use']}")
        print_bytes_in_use(run, svc, devs[:1])
        check(run, svc)
        bf16 = {r.rid: r.tokens for r in run.results}
        del run
        gc.collect()
        run = serve_once(serve, compile_s, "--archs", svc)  # int8 default
        same = total = 0
        for r in run.results:
            a, b = bf16[r.rid], r.tokens
            agree = int(np.cumprod(a == b).sum())
            same, total = same + agree, total + len(a)
        print(f"int8 KV token agreement with bf16 KV: {same}/{total} "
              f"tokens before each request's first divergence")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
