"""Sharding rules + a subprocess mini dry-run (the real 512-device sweep is
launch/dryrun.py; here a reduced config lowers+compiles on 8 placeholder
devices so CI exercises the whole path without the big compile bill)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import mesh as meshlib


def test_pick_only_shards_divisible_dims():
    mesh = jax.make_mesh((1,), ("model",))   # single-device mesh: no-op
    spec = meshlib._pick(mesh, (8, 16), {"model": [1]})
    assert spec == P(None, None)


def test_param_rules_shape_awareness():
    import numpy as np

    class FakeMesh:
        shape = {"data": 4, "model": 8}

    leafs = {
        "embed": {"embedding": jax.ShapeDtypeStruct((32000, 512), "float32"),
                  "unembed": jax.ShapeDtypeStruct((512, 32000), "float32")},
        "blocks": {"attn": {"wq": jax.ShapeDtypeStruct((4, 512, 256),
                                                       "float32")},
                   "mlp": {"w_down": jax.ShapeDtypeStruct((4, 1024, 512),
                                                          "float32")},
                   "ln1": {"w": jax.ShapeDtypeStruct((4, 512), "float32")}},
    }
    specs = meshlib.param_specs(FakeMesh, leafs, fsdp=True)
    assert specs["embed"]["embedding"] == P("model", "data")
    assert specs["embed"]["unembed"] == P("data", "model")
    assert specs["blocks"]["attn"]["wq"] == P(None, "data", "model")
    assert specs["blocks"]["mlp"]["w_down"] == P(None, "model", "data")
    assert specs["blocks"]["ln1"]["w"] == P()   # norms replicate


def test_cache_specs_prefer_heads_then_headdim():
    class FakeMesh:
        shape = {"data": 4, "model": 8}

    cache = {"k": jax.ShapeDtypeStruct((2, 8, 64, 8, 128), "float32"),
             "len": jax.ShapeDtypeStruct((), "int32")}
    specs = meshlib.cache_specs(FakeMesh, cache)
    assert specs["k"] == P(None, "data", None, "model", None)
    # kv=3 heads not divisible by 8 -> head_dim picked instead
    cache2 = {"k": jax.ShapeDtypeStruct((2, 8, 64, 3, 128), "float32"),
              "len": jax.ShapeDtypeStruct((), "int32")}
    specs2 = meshlib.cache_specs(FakeMesh, cache2)
    assert specs2["k"] == P(None, "data", None, None, "model")


def test_batch_specs_replicate_batch_one():
    class FakeMesh:
        shape = {"data": 4, "model": 8}

    specs = meshlib.batch_specs(
        FakeMesh, {"token": jax.ShapeDtypeStruct((1,), "int32")})
    assert specs["token"] == P(None)


_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, sys
    import jax
    from repro.configs import get_config, reduced
    from repro.launch import mesh as meshlib
    from repro.launch import steps as steplib
    from repro.models.config import SHAPES_BY_NAME
    import repro.configs as C

    arch, shape_name = sys.argv[1], sys.argv[2]
    small = reduced(get_config(arch), d_model=128, num_heads=4,
                    num_kv_heads=4, head_dim=32, d_ff=256, vocab_size=512)
    # patch the registry so build_step sees the reduced config
    C.ARCHS[arch] = small
    shape = dataclasses.replace(SHAPES_BY_NAME[shape_name],
                                seq_len=64, global_batch=8)
    steplib.SHAPES_BY_NAME = dict(SHAPES_BY_NAME)
    steplib.SHAPES_BY_NAME[shape_name] = shape
    mesh = meshlib.make_mesh((2, 4), ("data", "model"))
    bundle = steplib.build_step(arch, shape_name, mesh, microbatches=2)
    lowered = steplib.lower_step(bundle)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    print(json.dumps({"ok": True, "flops": float(ca.get("flops", 0))}))
""")


@pytest.mark.parametrize("arch,shape", [
    ("codeqwen1.5-7b", "train_4k"),
    ("mixtral-8x7b", "prefill_32k"),
    ("mamba2-2.7b", "decode_32k"),
    ("zamba2-7b", "long_500k"),
])
def test_mini_dryrun_subprocess(arch, shape, tmp_path):
    prog = tmp_path / "prog.py"
    prog.write_text(_SUBPROCESS_PROG)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, str(prog), arch, shape],
                         capture_output=True, text=True, timeout=540,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"]
