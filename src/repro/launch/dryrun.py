import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape) combination on the production meshes, print
memory_analysis / cost_analysis, and persist the roofline terms.

MUST be imported before any other jax-touching module — the two lines above
run before all imports so jax initializes with 512 placeholder devices.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
  python -m repro.launch.dryrun --all --both-meshes

Results land in one JSON per (arch, shape, mesh) so the sweep is
resumable; benchmarks/roofline reads these JSONs.
"""
import argparse       # noqa: E402
import json           # noqa: E402
import sys            # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402

from repro.configs import ARCH_IDS, SHAPES_BY_NAME, config_for_shape  # noqa: E402
from repro.launch import mesh as meshlib                   # noqa: E402
from repro.launch.steps import build_step, lower_step      # noqa: E402
from repro.roofline.analysis import (DRYRUN_DEVICE_KIND,   # noqa: E402
                                     analyze_compiled, model_flops_estimate)
from repro.roofline.analytic import traffic                # noqa: E402


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            out_dir: str, fsdp_params: bool = True,
            pad_vocab: int = 0, serve_2d_tp: bool = False,
            microbatches: int = 0, variant: str = "",
            mesh_shape: str = "", act_shard: str = "auto",
            fuse_proj: bool = False, expert_parallel: bool = False,
            verbose: bool = True) -> dict:
    mesh_tag = "pod512" if multi_pod else "pod256"
    if mesh_shape:
        mesh_tag = "mesh" + mesh_shape.replace(",", "x")
    vtag = f"_{variant}" if variant else ""
    name = f"{arch}|{shape_name}|{mesh_tag}{vtag}"
    out_path = os.path.join(out_dir,
                            f"{arch}_{shape_name}_{mesh_tag}{vtag}.json")
    if os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)

    if mesh_shape:
        dims = tuple(int(x) for x in mesh_shape.split(","))
        mesh = meshlib.make_mesh(dims, ("data", "model")[:len(dims)]
                                 if len(dims) == 2
                                 else ("pod", "data", "model"))
    else:
        mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    asm = None if act_shard == "auto" else (act_shard == "on")
    if fuse_proj:
        import dataclasses as _dc
        import repro.configs as _C
        _C.ARCHS[arch] = _dc.replace(_C.ARCHS[arch],
                                     fused_projections=True)
    bundle = build_step(arch, shape_name, mesh, fsdp_params=fsdp_params,
                        pad_vocab_multiple=pad_vocab or None,
                        serve_2d_tp=serve_2d_tp,
                        act_shard_model=asm,
                        expert_parallel=expert_parallel,
                        microbatches=microbatches or None)
    lowered = lower_step(bundle)
    t_lower = time.time() - t0
    hlo_text = lowered.as_text()
    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if verbose:
        print(f"[{name}] memory_analysis: {mem}")
        interesting = {k: v for k, v in (cost or {}).items()
                       if k in ("flops", "bytes accessed")}
        print(f"[{name}] cost_analysis: {interesting}")

    cfg = config_for_shape(arch, shape_name)
    shape = SHAPES_BY_NAME[shape_name]
    pod_ax = mesh.shape.get("pod", 1)
    tb = traffic(cfg, shape, data_ax=mesh.shape["data"],
                 model_ax=mesh.shape["model"], pod_ax=pod_ax,
                 microbatches=bundle.microbatches,
                 optimizer=(bundle.optimizer if bundle.optimizer != "none"
                            else "adamw"),
                 fsdp=fsdp_params, serve_2d_tp=serve_2d_tp)
    roof = analyze_compiled(name, compiled, chips, DRYRUN_DEVICE_KIND,
                            model_flops=model_flops_estimate(cfg, shape),
                            hlo_text=compiled.as_text(),
                            analytic_traffic=tb)
    hbm_used = (float(getattr(mem, "argument_size_in_bytes", 0))
                + float(getattr(mem, "temp_size_in_bytes", 0))
                + float(getattr(mem, "output_size_in_bytes", 0))
                - float(getattr(mem, "alias_size_in_bytes", 0)))
    record = dict(
        roof.to_dict(), arch=arch, shape=shape_name, mesh=mesh_tag,
        hbm_used_bytes=hbm_used, fits_hbm=bool(hbm_used <= 16e9),
        step=bundle.name, lower_s=t_lower, compile_s=t_compile,
        long_context_variant=(shape_name == "long_500k"
                              and cfg.sliding_window is not None
                              and config_for_shape(arch, "train_4k")
                              .sliding_window is None),
        ok=True)
    os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    if verbose:
        print(f"[{name}] compute={roof.compute_s:.4g}s "
              f"memory={roof.memory_s:.4g}s coll={roof.collective_s:.4g}s "
              f"dominant={roof.dominant} useful={roof.useful_flops_ratio:.3f}"
              f" (lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES_BY_NAME))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate weight rows over data axis (pure-TP)")
    ap.add_argument("--pad-vocab", type=int, default=0,
                    help="pad vocab_size to a multiple (hillclimb)")
    ap.add_argument("--serve-2d-tp", action="store_true",
                    help="decode with replicated batch / 2D-TP weights")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--variant", default="",
                    help="tag for the output filename (hillclimb runs)")
    ap.add_argument("--mesh-shape", default="",
                    help="override mesh, e.g. 4,64 (data,model)")
    ap.add_argument("--act-shard", default="auto",
                    choices=["auto", "on", "off"])
    ap.add_argument("--fuse-proj", action="store_true",
                    help="fused QKV + gate|up projections (hillclimb)")
    ap.add_argument("--expert-parallel", action="store_true",
                    help="shard MoE experts over the model axis (hillclimb)")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args(argv)

    combos = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES_BY_NAME:
                combos.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for multi_pod in meshes:
        for arch, shape in combos:
            try:
                run_one(arch, shape, multi_pod=multi_pod, out_dir=args.out,
                        fsdp_params=not args.no_fsdp,
                        pad_vocab=args.pad_vocab,
                        serve_2d_tp=args.serve_2d_tp,
                        microbatches=args.microbatches,
                        variant=args.variant, mesh_shape=args.mesh_shape,
                        act_shard=args.act_shard,
                        fuse_proj=args.fuse_proj,
                        expert_parallel=args.expert_parallel)
            except Exception as e:   # noqa: BLE001 — record and continue
                traceback.print_exc()
                failures.append((arch, shape, multi_pod, repr(e)[:200]))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f_ in failures:
            print("  ", f_)
        return 1
    print(f"\nall {len(combos) * len(meshes)} combos lowered + compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
