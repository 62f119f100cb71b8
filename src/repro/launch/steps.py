"""Step builders: one compiled function per (arch x input-shape x mesh).

``build_step`` returns a StepBundle with the jitted function, the
ShapeDtypeStruct argument tree (no device allocation), and the
in/out shardings — exactly what dryrun.py lowers and what train.py /
serve.py execute on real hardware.

Shape -> step mapping:
  train_4k               -> train_step (loss + grads + optimizer update)
  prefill_32k            -> serve_prefill (logits of last position + cache)
  decode_32k / long_500k -> serve_decode (ONE token vs a seq_len cache)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.configs import config_for_shape
from repro.models.config import ModelConfig, SHAPES_BY_NAME, ShapeSpec
from repro.models.registry import input_specs, model_api
from repro.training.optimizer import get_optimizer
from repro.training.train_step import make_train_step

from . import mesh as meshlib

ADAFACTOR_THRESHOLD = 50e9     # params above this train with adafactor


def choose_optimizer(cfg: ModelConfig) -> str:
    return "adafactor" if cfg.param_count() > ADAFACTOR_THRESHOLD \
        else "adamw"


def choose_microbatches(cfg: ModelConfig, shape: ShapeSpec,
                        batch_shards: int = 16) -> int:
    if shape.kind != "train":
        return 1
    if cfg.param_count() > ADAFACTOR_THRESHOLD:
        # MoE dispatch/combine transients scale with the microbatch; 16
        # keeps grok-314b near the 16 GB/chip HBM line (§Dry-run)
        k = 16
    elif cfg.param_count() > 5e9:
        k = 4
    else:
        k = 2
    # each microbatch must still shard evenly over the batch axes — on the
    # 512-chip mesh (32 batch shards) k=16 would leave 16-row microbatches
    # replicated across pods (observed +7 GB/chip, EXPERIMENTS.md §Dry-run)
    while k > 1 and (shape.global_batch // k) % batch_shards != 0:
        k //= 2
    return k


@dataclasses.dataclass
class StepBundle:
    name: str
    fn: Callable                     # jitted (already wrapped with shardings)
    args: Tuple[Any, ...]            # ShapeDtypeStruct pytrees
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    cfg: ModelConfig
    shape: ShapeSpec
    mesh: Mesh
    microbatches: int = 1
    optimizer: str = "none"


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def build_step(arch: str, shape_name: str, mesh: Mesh, *,
               fsdp_params: bool = True,
               microbatches: Optional[int] = None,
               optimizer_name: Optional[str] = None,
               pad_vocab_multiple: Optional[int] = None,
               serve_2d_tp: bool = False,
               act_shard_model: Optional[bool] = None,
               expert_parallel: bool = False,
               impl: Optional[str] = None) -> StepBundle:
    cfg = config_for_shape(arch, shape_name)
    if pad_vocab_multiple:
        # §Perf hillclimb: pad the vocab so the lm-head/embedding shard
        # over the model axis (minicpm's 122753 is unshardable -> full
        # f32 logits all-reduced per loss chunk)
        v = -(-cfg.vocab_size // pad_vocab_multiple) * pad_vocab_multiple
        cfg = dataclasses.replace(cfg, vocab_size=v)
    shape = SHAPES_BY_NAME[shape_name]
    api = model_api(cfg)
    key = jax.random.PRNGKey(0)

    params_shape = jax.eval_shape(lambda k: api.init(k, cfg), key)
    pspecs = meshlib.param_specs(mesh, params_shape, fsdp=fsdp_params,
                                 expert_parallel=expert_parallel)
    psharding = meshlib.named(mesh, pspecs)

    batch = input_specs(cfg, shape)
    if act_shard_model is None:
        # d-sharded carries only pay off when remat storage is the binding
        # constraint (the 100B+ models); small models lose more to the
        # reshard collectives than they save (EXPERIMENTS.md §Perf)
        act_shard_model = cfg.param_count() > ADAFACTOR_THRESHOLD
    meshlib.set_activation_mesh(mesh, shard_model=act_shard_model)

    if shape.kind == "train":
        opt_name = optimizer_name or choose_optimizer(cfg)
        opt = get_optimizer(opt_name)
        batch_shards = 1
        for ax in ("pod", "data"):
            batch_shards *= mesh.shape.get(ax, 1)
        nmb = microbatches or choose_microbatches(cfg, shape, batch_shards)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        ospecs = meshlib.opt_state_specs(mesh, opt_shape, pspecs)
        osharding = meshlib.named(mesh, ospecs)
        bspecs = meshlib.batch_specs(mesh, batch)
        bsharding = meshlib.named(mesh, bspecs)
        # bf16 grad accumulation for 100B+ configs: the fp32 accumulator
        # chain alone (grads + moments + update temps) would exceed
        # 16 GB/chip on the single pod (EXPERIMENTS.md §Dry-run)
        accum = jnp.bfloat16 if cfg.param_count() > ADAFACTOR_THRESHOLD \
            else jnp.float32
        step = make_train_step(cfg, opt, num_microbatches=nmb,
                               accum_dtype=accum, impl=impl)
        out_shardings = (psharding, osharding, None)
        fn = jax.jit(step,
                     in_shardings=(psharding, osharding, bsharding),
                     out_shardings=out_shardings,
                     donate_argnums=(0, 1))
        args = (params_shape, opt_shape, batch)
        in_sh = (psharding, osharding, bsharding)
        name = f"{arch}:{shape_name}:train[{opt_name},mb={nmb}]"
    elif shape.kind == "prefill":
        bspecs = meshlib.batch_specs(mesh, batch)
        bsharding = meshlib.named(mesh, bspecs)
        cache_size = shape.seq_len

        def prefill(params, b):
            return api.prefill(params, cfg, b, cache_size=cache_size,
                               impl=impl)

        cache_shape = jax.eval_shape(
            lambda: api.init_cache(cfg, shape.global_batch, cache_size))
        cspecs = meshlib.cache_specs(mesh, cache_shape)
        csharding = meshlib.named(mesh, cspecs)
        out_shardings = (None, csharding)
        fn = jax.jit(prefill, in_shardings=(psharding, bsharding),
                     out_shardings=out_shardings)
        args = (params_shape, batch)
        in_sh = (psharding, bsharding)
        name = f"{arch}:{shape_name}:prefill"
    else:  # decode
        cache_shape = jax.eval_shape(
            lambda: api.init_cache(cfg, shape.global_batch, shape.seq_len))
        # the cache arrives "full": len = seq_len
        cspecs = meshlib.cache_specs(mesh, cache_shape,
                                     replicate_batch=serve_2d_tp)
        csharding = meshlib.named(mesh, cspecs)
        token = batch["token"]
        tspec = meshlib.batch_specs(mesh, {"token": token},
                                    replicate_batch=serve_2d_tp)["token"]
        tsharding = NamedSharding(mesh, tspec)

        def decode(params, tok, cache):
            return api.decode_step(params, cfg, tok, cache, impl=impl)

        out_shardings = (None, csharding)
        fn = jax.jit(decode,
                     in_shardings=(psharding, tsharding, csharding),
                     out_shardings=out_shardings,
                     donate_argnums=(2,))
        args = (params_shape, token, cache_shape)
        in_sh = (psharding, tsharding, csharding)
        name = f"{arch}:{shape_name}:decode" + \
            ("[2dtp]" if serve_2d_tp else "")

    return StepBundle(name=name, fn=fn, args=args, in_shardings=in_sh,
                      out_shardings=out_shardings, cfg=cfg, shape=shape,
                      mesh=mesh,
                      microbatches=nmb if shape.kind == "train" else 1,
                      optimizer=(opt_name if shape.kind == "train"
                                 else "none"))


def lower_step(bundle: StepBundle):
    with bundle.mesh:
        return bundle.fn.lower(*bundle.args)
