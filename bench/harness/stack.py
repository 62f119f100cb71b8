"""Build the serving stack the way the launcher does (``launch/serve.py``):
one edge server, the EPARA plan from ``EdgeCloudControlPlane`` (allocator
and placement), one ``ServiceRuntime`` with the category's default knobs
(but the slot length and the prefill chunk, which a mix may set) in an
``EparaServingEngine``, driven through ``ClusterSupervisor``.  The
weights are the benchmark's own (``weights.py``), laid out by the cell's
model family (``bench/families/<family>.py``)."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from . import weights as wlib

BLOCK_SIZE = 32                 # the launcher's default arena block


def model_config(config: Dict[str, Any], family):
    """The program's config for the arch, with every size the file gives."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(config["arch"]),
                               **family.program_config(config))


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


def build(config: Dict[str, Any], family, seed: int, chips: int, tracer,
          max_seq_len: int, prefill_chunk: Optional[int] = None) -> Stack:
    import jax
    from repro.core import EdgeCloudControlPlane, ServerSpec
    from repro.launch.serve import service_spec_for
    from repro.serving.engine import EparaServingEngine, ServiceRuntime
    from repro.serving.failover import ClusterSupervisor, RetryPolicy

    cfg = model_config(config, family)
    arch = cfg.name
    servers = [ServerSpec(sid=0, num_gpus=4)]
    cp = EdgeCloudControlPlane(servers, {arch: service_spec_for(cfg)})
    cp.run_placement({(arch, 0): 4.0})
    mesh = shardings = None
    layout = family.layout(family.dims(config, "bf16"))
    if chips > 1:
        from repro.launch import mesh as meshlib
        mesh = meshlib.make_mesh((1, chips), ("data", "model"))
        shardings = meshlib.named(mesh, meshlib.param_specs(
            mesh, wlib.shapes(layout), fsdp=False))
    params = wlib.init_weights(layout, seed, shardings)
    jax.block_until_ready(params)
    rt = ServiceRuntime(cfg, params, cp.plans[arch], mode="continuous",
                        kvcache_impl="paged",
                        max_seq_len=max_seq_len,
                        prefill_chunk=prefill_chunk,
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
