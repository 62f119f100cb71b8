"""Operation and byte counts per kernel and per step (the dense family's,
``bench/families/dense.py``) match hand counts at minicpm-2b and
codeqwen1.5-7b widths."""
import json
import math

import jax
import pytest

import bench_tree
from harness import cells, counts, weights

dense = cells.load_family(bench_tree.ROOT, "dense")

MINICPM = dense.Dims(layers=40, d_model=2304, heads=36, kv_heads=36,
                     head_dim=64, d_ff=5760, vocab=122753, tied=True,
                     qkv_bias=False, kv_dtype="int8", eps=1e-5, theta=1e4)
CODEQWEN = dense.Dims(layers=32, d_model=4096, heads=32, kv_heads=4,
                      head_dim=128, d_ff=13440, vocab=92416, tied=False,
                      qkv_bias=True, kv_dtype="bf16", eps=1e-5, theta=1e6)


def test_minicpm_hand_counts():
    d = MINICPM
    # q, k, v, o: 4 x 2304^2; gate, up, down: 3 x 2304 x 5760
    assert d.layer_matmul_params == 4 * 2304 ** 2 + 3 * 2304 * 5760 \
        == 61_046_784
    # int8 K and V rows (36 x 64 each) plus one f32 scale per head each
    assert d.kv_token_bytes == 2 * 36 * 64 + 2 * 36 * 4 == 4896
    # weights read once: 40 layers (+2 norms), final norm, tied head
    assert d.weights_read_bytes == 2 * (40 * (61_046_784 + 2 * 2304)
                                        + 2304 + 2304 * 122753)
    ops, byt = dense.decode_step(d, [100, 300])
    tok = 2 * (40 * 61_046_784 + 2304 * 122753)
    assert ops == 2 * tok + 4 * 40 * 36 * 64 * (100 + 300)
    assert byt == (d.weights_read_bytes + 40 * 4896 * (99 + 299)
                   + 40 * 4896 * 2 + 2 * 2304 * 2)
    kops, kbyt = dense.paged_decode_kernel(d, [100, 300])
    assert kops == 4 * 40 * 36 * 64 * 400
    assert kbyt == 40 * (4896 * 400 + 2 * 2 * 36 * 64 * 2)


def test_codeqwen_hand_counts():
    d = CODEQWEN
    # grouped-query attention: 4 KV heads of 128 serve 32 query heads
    assert d.layer_matmul_params == (2 * 4096 ** 2 + 2 * 4096 * 512
                                     + 3 * 4096 * 13440)
    assert d.layer_params == d.layer_matmul_params + 2 * 4096 + 4096 \
        + 2 * 512
    assert d.kv_token_bytes == 2 * 4 * 128 * 2
    # a 64-token chunk after 128 cached tokens: causal keys
    # sum_{i=1..64} (128 + i) = 64*128 + 64*65/2
    keys = 64 * 128 + 64 * 65 // 2
    ops, byt = dense.chunk_step(d, 128, 64)
    assert ops == (2 * 64 * 32 * d.layer_matmul_params + 2 * 4096 * 92416
                   + 4 * 32 * 32 * 128 * keys)
    assert byt == (d.weights_read_bytes + 32 * d.kv_token_bytes * 192
                   + 2 * 4096 * 64)
    kops, kbyt = dense.paged_chunk_kernel(d, 128, 64)
    assert kops == 4 * 32 * 32 * 128 * keys
    assert kbyt == 32 * (d.kv_token_bytes * 192 + 2 * 64 * 32 * 128 * 2)


@pytest.mark.parametrize("dims", [MINICPM, CODEQWEN], ids=["minicpm",
                                                            "codeqwen"])
def test_weights_read_match_the_weights_drawn(dims):
    n = sum(math.prod(s[0]) for s in jax.tree.leaves(
        dense.layout(dims), is_leaf=weights._is_spec))
    emb = dims.vocab * dims.d_model
    # every weight is read once a step except the untied input table,
    # of which a step gathers only its tokens' rows
    assert dims.weights_read_bytes == 2 * (n - (0 if dims.tied else emb))


def test_roofline_share_names_its_bound():
    pct, bound = counts.roofline_share(197e12, 1.0, 2.0, 197e12, 819e9)
    assert pct == pytest.approx(50.0) and bound == "compute"
    pct, bound = counts.roofline_share(1.0, 819e9, 4.0, 197e12, 819e9)
    assert pct == pytest.approx(25.0) and bound == "memory"


@pytest.mark.parametrize("name,dims", [("minicpm-2b", MINICPM),
                                       ("codeqwen1.5-7b", CODEQWEN)])
def test_configuration_files_give_the_hand_counted_sizes(name, dims):
    cfg = json.loads((bench_tree.ROOT / "bench" / "configs"
                      / f"{name}.json").read_text())
    assert dense.dims(cfg, dims.kv_dtype) == dims
