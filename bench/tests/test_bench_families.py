"""A model family is a file (``bench/families/<family>.py``): the dense
family's reference forward is the one the harness held before it moved
there, its weight layout is the program's own parameter tree, and a
family that is not there is named."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tree
from harness import cells, stack, weights
from harness.reference import HI, _mm, _rms, _rope

dense = cells.load_family(bench_tree.ROOT, "dense")
CONFIGS = ["minicpm-2b", "codeqwen1.5-7b"]


def _tiny_config(name):
    cfg = json.loads((bench_tree.ROOT / "bench" / "configs"
                      / f"{name}.json").read_text())
    cfg["config"].update(bench_tree.TINY)
    return cfg


def _old_logits(params, dims, tokens, *, eps, theta, low=False):
    """The dense reference forward as the harness held it before the
    family split (``harness/reference.py``), copied unchanged."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    L = tokens.shape[0]
    H, Hk, D = dims.heads, dims.kv_heads, dims.head_dim
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def layer(x, lp):
        lp = f32(lp)
        at = lp["attn"]
        h = _rms(x, lp["ln1"]["w"], eps)
        q = _mm(h, at["wq"], low) + at.get("bq", 0.0)
        k = _mm(h, at["wk"], low) + at.get("bk", 0.0)
        v = _mm(h, at["wv"], low) + at.get("bv", 0.0)
        q = _rope(q.reshape(L, H, D), theta)
        k = _rope(k.reshape(L, Hk, D), theta)
        v = v.reshape(L, Hk, D)
        k, v = jnp.repeat(k, H // Hk, 1), jnp.repeat(v, H // Hk, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * D ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(L, H * D)
        x = x + _mm(o, at["wo"], low)
        h = _rms(x, lp["ln2"]["w"], eps)
        m = lp["mlp"]
        g = jax.nn.silu(_mm(h, m["w_gate"], low)) * _mm(h, m["w_up"], low)
        return x + _mm(g, m["w_down"], low), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x, params["ln_f"]["w"].astype(jnp.float32), eps)
    if dims.tied:
        head = params["embed"]["embedding"].astype(jnp.float32).T
    else:
        head = params["embed"]["unembed"].astype(jnp.float32)
    return _mm(x, head, low)


@pytest.mark.parametrize("low", [False, True], ids=["sound", "control"])
@pytest.mark.parametrize("name", CONFIGS)
def test_dense_logits_equal_the_old_reference_bitwise(name, low):
    cfg = _tiny_config(name)
    dims = dense.dims(cfg, "int8")
    params = weights.init_weights(dense.layout(dims), 2 ** 31 + 77)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, dims.vocab, 40), jnp.int32)
    with jax.default_matmul_precision("highest"):
        new = jax.jit(lambda p, t: dense.logits(p, dims, t, low=low))(
            params, tokens)
        old = jax.jit(lambda p, t: _old_logits(
            p, dims, t, eps=float(cfg["config"]["rms_norm_eps"]),
            theta=float(cfg["config"]["rope_theta"]), low=low))(
            params, tokens)
    assert np.array_equal(np.asarray(new), np.asarray(old))


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_layout_is_the_program_parameter_tree(name):
    from repro.models import registry
    cfg = _tiny_config(name)
    program = stack.model_config(cfg, dense)
    init = registry.model_api(program).init
    tree = jax.eval_shape(lambda k: init(k, program), jax.random.PRNGKey(0))
    ours = weights.shapes(dense.layout(dense.dims(cfg, "bf16")))
    assert jax.tree.structure(ours) == jax.tree.structure(tree)
    assert [a.shape for a in jax.tree.leaves(ours)] == \
        [a.shape for a in jax.tree.leaves(tree)]


def test_a_missing_family_is_named(tmp_path):
    root = bench_tree.tiny_tree(tmp_path)
    path = root / "bench" / "families" / "nosuch.py"
    with pytest.raises(FileNotFoundError, match=str(path)):
        cells.load_family(root, "nosuch")
    cfg_path = root / "bench" / "configs" / "minicpm-2b.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["family"] = "nosuch"
    cfg_path.write_text(json.dumps(cfg))
    with pytest.raises(FileNotFoundError, match=str(path)):
        cells.load_cell(root, "minicpm-2b.streams")
