"""Build the serving stack the way the launcher does (``launch/serve.py``):
one edge server, the EPARA plan from ``EdgeCloudControlPlane`` (allocator
and placement), one ``ServiceRuntime`` with the category's default knobs
in an ``EparaServingEngine``, driven through ``ClusterSupervisor``.  The
weights are the benchmark's own (``weights.py``)."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from .counts import Dims
from . import weights as wlib

BLOCK_SIZE = 32                 # the launcher's default arena block
# config-file key -> ModelConfig field
FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
          "tie_word_embeddings": "tie_embeddings", "qkv_bias": "qkv_bias"}


def model_config(config: Dict[str, Any]):
    """The program's config for the arch, with every size the file gives."""
    from repro.configs import get_config
    over = {FIELDS[k]: v for k, v in config["config"].items() if k in FIELDS}
    return dataclasses.replace(get_config(config["arch"]), **over)


def dims_of(config: Dict[str, Any], kv_dtype: str) -> Dims:
    c = config["config"]
    return Dims(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                tied=bool(c["tie_word_embeddings"]),
                qkv_bias=bool(c.get("qkv_bias", False)), kv_dtype=kv_dtype)


@dataclasses.dataclass
class Stack:
    arch: str
    cfg: Any
    params: Any
    mesh: Any
    cp: Any
    engine: Any
    runtime: Any
    supervisor: Any

    def free_program_state(self) -> None:
        """Drop the serving state (arena, runtime, control plane); the
        weights stay for the reference."""
        self.engine = self.runtime = self.supervisor = self.cp = None


def build(config: Dict[str, Any], seed: int, chips: int,
          tracer) -> Stack:
    import jax
    from repro.core import EdgeCloudControlPlane, ServerSpec
    from repro.launch.serve import service_spec_for
    from repro.serving.engine import EparaServingEngine, ServiceRuntime
    from repro.serving.failover import ClusterSupervisor, RetryPolicy

    cfg = model_config(config)
    arch = cfg.name
    servers = [ServerSpec(sid=0, num_gpus=4)]
    cp = EdgeCloudControlPlane(servers, {arch: service_spec_for(cfg)})
    cp.run_placement({(arch, 0): 4.0})
    mesh = shardings = None
    dims = dims_of(config, "bf16")
    if chips > 1:
        from repro.launch import mesh as meshlib
        mesh = meshlib.make_mesh((1, chips), ("data", "model"))
        shardings = meshlib.named(mesh, meshlib.param_specs(
            mesh, wlib.shapes(dims), fsdp=False))
    params = wlib.init_weights(dims, seed, shardings)
    jax.block_until_ready(params)
    rt = ServiceRuntime(cfg, params, cp.plans[arch], mode="continuous",
                        kvcache_impl="paged",
                        max_seq_len=int(config["max_seq_len"]),
                        block_size=BLOCK_SIZE, mesh=mesh, tracer=tracer)
    engine = EparaServingEngine()
    engine.deploy(arch, rt)
    cp.publish_all(0.0)
    cp.sync_step(0.0)
    # one server: a retry timeout could only re-run a request on the host
    # already serving it
    sup = ClusterSupervisor(cp, {0: engine},
                            retry=RetryPolicy(base_timeout_s=1e9))
    return Stack(arch=arch, cfg=cfg, params=params, mesh=mesh, cp=cp,
                 engine=engine, runtime=rt, supervisor=sup)


def arena_slots(stack: Stack) -> Optional[int]:
    caps = [g.arena.capacity for g in stack.runtime.groups.values()
            if g.arena is not None]
    return min(caps) if caps else None
