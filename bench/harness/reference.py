"""The plain reference forward and the lower-precision control.

Written from the layer equations alone; it imports nothing of the program
and takes only the weights the benchmark drew.  Dense decoder, as the
program's dense family computes it:

    x = E[tokens]
    per layer:  h = rms(x) * g1;  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
                q, k = rope(q), rope(k)    (half-split rotation, theta)
                x = x + softmax(q k^T / sqrt(D), causal) v Wo
                h = rms(x) * g2;  x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x) * gf) E^T   (tied)   or   (rms(x) * gf) U

All in float32 at ``highest`` matmul precision.  The control computes
every matrix product of the same forward with both operands rounded to
float8 (e4m3, scaled per row of the activations and per output column of
the weights): the step below bf16 that a later change might take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .counts import Dims

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, low):
    """a (..., i) @ w (i, o) in float32, or with both rounded to fp8."""
    if low:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.dot(a, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (L, H, D) at positions 0..L-1."""
    L, _, D = x.shape
    half = D // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits(params, dims: Dims, tokens, *, eps: float, theta: float,
           low: bool = False):
    """(L, V) float32 logits of one token sequence."""
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


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta",
                                             "control"))
def _gaps(params, seq, n_prompt, served, *, dims, eps, theta, control):
    """Per served position j (row ``n_prompt - 1 + j`` predicts served
    token j): how far below the reference's best logit the judged token
    lies, in units of that row's reference-logit standard deviation.  The
    judged token is the served one, or under ``control`` the token the
    fp8 forward puts first."""
    ref = logits(params, dims, seq, eps=eps, theta=theta)
    rows = jax.lax.dynamic_slice_in_dim(ref, n_prompt - 1, served.shape[0])
    if control:
        low = logits(params, dims, seq, eps=eps, theta=theta, low=True)
        lrows = jax.lax.dynamic_slice_in_dim(low, n_prompt - 1,
                                             served.shape[0])
        judged = jnp.argmax(lrows, axis=1)
    else:
        judged = served
    got = jnp.take_along_axis(rows, judged[:, None], axis=1)[:, 0]
    return (rows.max(axis=1) - got) / rows.std(axis=1)


def served_gaps(params, dims: Dims, samples, *, width: int, served_max: int,
                eps: float, theta: float, control: bool = False):
    """``samples``: (prompt, served tokens) pairs.  Each is run once,
    teacher-forced, padded to ``width`` so one program serves all (causal:
    the padding never reaches the rows read).  Returns one array of gaps
    per sample."""
    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, served in samples:
            seq = np.zeros((width,), np.int32)
            fed = np.concatenate([prompt, served[:-1]])
            seq[:len(fed)] = fed
            pad = np.zeros((served_max,), np.int32)
            pad[:len(served)] = served
            g = _gaps(params, jnp.asarray(seq), jnp.int32(len(prompt)),
                      jnp.asarray(pad), dims=dims, eps=eps, theta=theta,
                      control=control)
            out.append(np.asarray(g)[:len(served)])
    return out
