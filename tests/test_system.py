"""End-to-end system behaviour: the full EPARA pipeline — allocator ->
placement -> sync -> handler -> live JAX serving — plus the launchers'
public entry points."""
import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced
from repro.core import (EdgeCloudControlPlane, Outcome, Request, ServerSpec,
                        ServiceSpec, Sensitivity)
from repro.models.registry import model_api
from repro.serving.engine import (EparaServingEngine, GenerationRequest,
                                  ServiceRuntime)


def _specs():
    return {
        "chat": ServiceSpec("chat", flops_per_request=1e10,
                            weights_bytes=2e8, vram_bytes=5e8,
                            slo_latency_s=1.0),
        "video": ServiceSpec("video", flops_per_request=5e9,
                             weights_bytes=1e8, vram_bytes=3e8,
                             sensitivity=Sensitivity.FREQUENCY,
                             slo_fps=30.0, slo_latency_s=0.2),
    }


def test_full_pipeline_serves_requests(dense_cfg):
    servers = [ServerSpec(sid=i, num_gpus=2) for i in range(2)]
    cp = EdgeCloudControlPlane(servers, _specs())
    demand = {(s, n): 10.0 for s in _specs() for n in range(2)}
    placements = cp.run_placement(demand)
    assert placements
    cp.publish_all(0.0)
    for _ in range(2):
        cp.sync_step(0.0)

    # live data plane: toy dense model stands in for both services
    params = model_api(dense_cfg).init(jax.random.PRNGKey(0), dense_cfg)
    engines = {s.sid: EparaServingEngine() for s in servers}
    for svc, sid in placements:
        if sid >= 0:
            engines[sid].deploy(svc, ServiceRuntime(dense_cfg, params,
                                                    cp.plans[svc]))
    served = 0
    for i in range(6):
        svc = list(_specs())[i % 2]
        req = Request(rid=i, service=svc, arrival_s=0.0, deadline_s=100.0)
        d = cp.handle(req, now=0.0, at_server=i % 2)
        assert d.outcome in (Outcome.LOCAL, Outcome.OFFLOAD,
                             Outcome.LOCAL_CROSS)
        target = d.destination if d.outcome == Outcome.OFFLOAD else i % 2
        if svc not in engines[target].runtimes:
            target = next(s for s, e in engines.items()
                          if svc in e.runtimes)
        engines[target].submit(svc, GenerationRequest(
            rid=i, tokens=np.arange(4, dtype=np.int32), max_new_tokens=2))
        served += 1
    results = []
    for e in engines.values():
        results.extend(e.drain())
    assert len(results) == served
    assert all(len(r.tokens) == 2 for r in results)


def test_serve_launcher_main():
    from repro.launch import serve
    rc = serve.main(["--archs", "codeqwen1.5-7b", "--servers", "2",
                     "--requests", "4", "--max-new-tokens", "2"])
    assert rc == 0


def test_train_launcher_main():
    from repro.launch import train
    rc = train.main(["--arch", "minicpm-2b", "--reduced", "--steps", "3",
                     "--batch", "2", "--seq", "32", "--log-every", "2"])
    assert rc == 0


def test_reduced_configs_are_smoke_sized():
    for arch in ARCH_IDS:
        cfg = reduced(get_config(arch))
        assert cfg.num_layers <= 2 or cfg.family == "hybrid"
        assert cfg.d_model <= 512
        if cfg.family == "moe":
            assert cfg.num_experts <= 4


def _run_py(code: str, **env_over) -> str:
    import os
    import subprocess
    import sys
    env = dict(os.environ, **env_over)
    env["PYTHONPATH"] = (os.path.abspath("src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.strip().splitlines()[-1]


def test_serve_weights_depend_on_seed_not_process():
    """Launcher weights come from --seed and the arch id: the same in
    every process whatever its string-hash salt, different per seed."""
    from repro.launch.serve import init_params
    cfg = reduced(get_config("minicpm-2b"))
    code = ("from repro.configs import get_config, reduced\n"
            "from repro.launch.serve import init_params\n"
            "import hashlib, jax, numpy as np\n"
            "p = init_params(reduced(get_config('minicpm-2b')), 7)\n"
            "print(hashlib.sha256(b''.join(np.asarray(x).tobytes()"
            " for x in jax.tree.leaves(p))).hexdigest())\n")
    digests = {_run_py(code, PYTHONHASHSEED=str(h), JAX_PLATFORMS="cpu")
               for h in (1, 2)}
    here = hashlib.sha256(b"".join(
        np.asarray(x).tobytes()
        for x in jax.tree.leaves(init_params(cfg, 7)))).hexdigest()
    assert digests == {here}
    other = init_params(cfg, 8)
    assert not all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(other), jax.tree.leaves(init_params(cfg, 7))))


def test_serve_launcher_shards_over_four_devices():
    """--pjit-decode on four (virtual CPU) devices: weights are born
    sharded over the (1, 4) mesh's model axis, the arena with them, the
    decode step compiles once, and the greedy tokens equal the
    one-device run's."""
    code = """
import json, jax, numpy as np
from repro.launch import serve
argv = ["--archs", "minicpm-2b", "--servers", "1", "--requests", "3",
        "--max-new-tokens", "4", "--prompt-len", "10,40"]
one = serve.serve(argv)
mp = serve.serve(argv + ["--pjit-decode"])
p = mp.params["minicpm-2b"]
wq = p["blocks"]["attn"]["wq"]
print(json.dumps({
    "rc": [one.exit_code, mp.exit_code],
    "same": {r.rid: r.tokens.tolist() for r in one.results}
            == {r.rid: r.tokens.tolist() for r in mp.results},
    "traces": mp.decode_traces,
    "wq_devices": len(wq.sharding.device_set),
    "wq_shard": list(wq.sharding.shard_shape(wq.shape)),
    "wq_shape": list(wq.shape)}))
"""
    import json
    rec = json.loads(_run_py(
        code, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert rec["rc"] == [0, 0] and rec["same"] and rec["traces"] == 1
    assert rec["wq_devices"] == 4
    assert rec["wq_shard"][-1] * 4 == rec["wq_shape"][-1]
