"""The dense decoder family: sizes, weight layout, plain reference forward
and the operation and byte counts, as the program's dense family
(``models/transformer.py``) computes it.

A family file gives the harness everything that depends on the layer
equations; the harness keeps what every family shares (drawing weights
from ``(shape, kind)`` specs, the served-token check, roofline shares,
peaks, trace reduction, traffic).  A configuration names its family with
``"family"``, and ``bench/families/<family>.py`` is loaded by path.  It
provides:

    program_config(config)   ModelConfig overrides from the file's sizes
    dims(config, kv_dtype)   a frozen, hashable record of the sizes
    layout(dims)             {path: (shape, kind)}, nested like the
                             program's parameter tree
    logits(params, dims, tokens, *, low=False)
                             the plain float32 forward; ``low`` its fp8
                             control
    decode_step, chunk_step, paged_decode_kernel, paged_chunk_kernel
                             (operations, bytes) the metrics read

Reference, in float32 at ``highest`` matmul precision:

    x = E[tokens]
    per layer:  h = rms(x) * g1;  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
                q, k = rope(q), rope(k)    (half-split rotation, theta)
                x = x + softmax(q k^T / sqrt(D), causal) v Wo
                h = rms(x) * g2;  x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x) * gf) E^T   (tied)   or   (rms(x) * gf) U

Counts: one multiply-add is 2 operations; a cache length ``n`` counts the
keys a query attends over, the new token's own key included.  They count
what the mathematics requires, never what an implementation happens to
move.  Under tensor parallelism over ``chips`` devices every matrix and
every head is split evenly, so each device's share of a count is the
count divided by ``chips``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from harness.counts import chunk_keys
from harness.reference import HI, _mm, _rms, _rope

# config-file key -> ModelConfig field
FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
          "tie_word_embeddings": "tie_embeddings", "qkv_bias": "qkv_bias"}


def program_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ``ModelConfig`` fields for every size the file gives."""
    return {FIELDS[k]: v for k, v in config["config"].items() if k in FIELDS}


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    kv_dtype: str              # "bf16" or "int8"
    eps: float                 # RMS norm epsilon
    theta: float               # rope base
    weight_bytes: int = 2      # bf16 weights and activations

    @property
    def layer_matmul_params(self) -> int:
        """Weights of one layer's matrix multiplications (q, k, v, o and
        the gated MLP's gate, up and down)."""
        d, a, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * a + 2 * d * kv + a * d + 3 * d * self.d_ff

    @property
    def layer_params(self) -> int:
        """Every weight of one layer: matrices, two norms, q/k/v biases."""
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim \
            if self.qkv_bias else 0
        return self.layer_matmul_params + 2 * self.d_model + bias

    @property
    def weights_read_bytes(self) -> int:
        """Bytes of weights one forward step must read once: every layer,
        the final norm and the output head (the embedding table itself
        when tied)."""
        return self.weight_bytes * (self.layers * self.layer_params
                                    + self.d_model
                                    + self.d_model * self.vocab)

    @property
    def kv_token_bytes(self) -> int:
        """Cache bytes of one token in one layer: K and V, plus the int8
        pools' per-row f32 scales."""
        e = 1 if self.kv_dtype == "int8" else self.weight_bytes
        per = 2 * self.kv_heads * self.head_dim * e
        if self.kv_dtype == "int8":
            per += 2 * self.kv_heads * 4
        return per

    def attn_ops(self, n_keys: int) -> int:
        """QK^T and PV of one query over ``n_keys`` keys, every layer."""
        return 4 * self.layers * self.heads * self.head_dim * n_keys

    @property
    def token_matmul_ops(self) -> int:
        """Operations of one token through every layer's matrices and the
        output head (attention over the cache excluded)."""
        return 2 * (self.layers * self.layer_matmul_params
                    + self.d_model * self.vocab)


def dims(config: Dict[str, Any], kv_dtype: str) -> Dims:
    c = config["config"]
    return Dims(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                tied=bool(c["tie_word_embeddings"]),
                qkv_bias=bool(c.get("qkv_bias", False)), kv_dtype=kv_dtype,
                eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]))


# -- weights ---------------------------------------------------------------
def layout(dims: Dims) -> Dict[str, Any]:
    """``{path: (shape, kind)}`` nested like the parameter tree (layers
    stacked on a leading axis)."""
    L, d, f = dims.layers, dims.d_model, dims.d_ff
    a, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    attn = {"wq": ((L, d, a), "matrix"), "wk": ((L, d, kv), "matrix"),
            "wv": ((L, d, kv), "matrix"), "wo": ((L, a, d), "matrix")}
    if dims.qkv_bias:
        attn.update(bq=((L, a), "bias"), bk=((L, kv), "bias"),
                    bv=((L, kv), "bias"))
    embed = {"embedding": ((dims.vocab, d), "embed")}
    if not dims.tied:
        embed["unembed"] = ((d, dims.vocab), "matrix")
    return {
        "embed": embed,
        "blocks": {
            "ln1": {"w": ((L, d), "norm")},
            "attn": attn,
            "ln2": {"w": ((L, d), "norm")},
            "mlp": {"w_gate": ((L, d, f), "matrix"),
                    "w_up": ((L, d, f), "matrix"),
                    "w_down": ((L, f, d), "matrix")},
        },
        "ln_f": {"w": ((d,), "norm")},
    }


# -- reference ---------------------------------------------------------------
def logits(params, dims: Dims, tokens, *, low: bool = False):
    """(L, V) float32 logits of one token sequence."""
    eps, theta = dims.eps, dims.theta
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


# -- counts ------------------------------------------------------------------
def model_ops_per_token(dims: Dims, n_keys: int) -> int:
    """Model operations of one token that attends over ``n_keys`` keys."""
    return dims.token_matmul_ops + dims.attn_ops(n_keys)


def decode_step(dims: Dims, lens: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of one fused decode step in which each live
    slot attends over ``lens[b]`` keys (its own new key included): the
    matrices once per live slot, the weights read once, each slot's
    earlier cache rows read and its new row written."""
    lens = list(lens)
    ops = sum(model_ops_per_token(dims, n) for n in lens)
    byt = (dims.weights_read_bytes
           + dims.layers * dims.kv_token_bytes * sum(n - 1 for n in lens)
           + dims.layers * dims.kv_token_bytes * len(lens)
           + dims.weight_bytes * dims.d_model * len(lens))   # embed rows
    return float(ops), float(byt)


def paged_decode_kernel(dims: Dims, lens: Iterable[int]) -> Tuple[float,
                                                                  float]:
    """(operations, bytes) of the paged decode attention kernel over every
    layer of one step: each live slot's query attends over its ``n`` keys,
    read from the pool; the query comes in and the output goes out."""
    lens = list(lens)
    ops = sum(dims.attn_ops(n) for n in lens)
    qo = 2 * dims.heads * dims.head_dim * dims.weight_bytes
    byt = dims.layers * (dims.kv_token_bytes * sum(lens) + qo * len(lens))
    return float(ops), float(byt)


def chunk_step(dims: Dims, start: int, n: int) -> Tuple[float, float]:
    """(operations, bytes) of one chunked-prefill call that adds ``n``
    prompt tokens after ``start`` cached ones and yields one row of
    logits: the matrices for ``n`` tokens, causal attention, the weights
    read once, the earlier rows read and the new rows written."""
    ops = (2 * n * dims.layers * dims.layer_matmul_params
           + 2 * dims.d_model * dims.vocab
           + 4 * dims.layers * dims.heads * dims.head_dim
           * chunk_keys(start, n))
    byt = (dims.weights_read_bytes
           + dims.layers * dims.kv_token_bytes * (start + n)
           + dims.weight_bytes * dims.d_model * n)
    return float(ops), float(byt)


def paged_chunk_kernel(dims: Dims, start: int, n: int) -> Tuple[float,
                                                                float]:
    """(operations, bytes) of the paged chunk attention kernel over every
    layer of one call: ``n`` causal queries after ``start`` cached keys,
    with the chunk's own rows already in the pool."""
    ops = 4 * dims.layers * dims.heads * dims.head_dim * chunk_keys(start, n)
    qo = 2 * n * dims.heads * dims.head_dim * dims.weight_bytes
    byt = dims.layers * (dims.kv_token_bytes * (start + n) + qo)
    return float(ops), float(byt)
