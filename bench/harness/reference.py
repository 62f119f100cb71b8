"""The served-token check against the plain reference, and the pieces
every family's reference forward shares.

A family's ``logits(params, dims, tokens, *, low=False)``
(``bench/families/<family>.py``) is written from its layer equations
alone; it imports nothing of the program and takes only the weights the
benchmark drew.  All in float32 at ``highest`` matmul precision.  Its
control (``low``) computes every matrix product of the same forward with
both operands rounded to float8 (e4m3, scaled per row of the activations
and per output column of the weights, ``_mm``): the step below bf16 that
a later change might take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


@functools.partial(jax.jit, static_argnames=("logits", "dims", "control"))
def _gaps(params, seq, n_prompt, served, *, logits, dims, control):
    """Per served position j (row ``n_prompt - 1 + j`` predicts served
    token j): how far below the reference's best logit the judged token
    lies, in units of that row's reference-logit standard deviation.  The
    judged token is the served one, or under ``control`` the token the
    fp8 forward puts first."""
    ref = logits(params, dims, seq)
    rows = jax.lax.dynamic_slice_in_dim(ref, n_prompt - 1, served.shape[0])
    if control:
        low = logits(params, dims, seq, low=True)
        lrows = jax.lax.dynamic_slice_in_dim(low, n_prompt - 1,
                                             served.shape[0])
        judged = jnp.argmax(lrows, axis=1)
    else:
        judged = served
    got = jnp.take_along_axis(rows, judged[:, None], axis=1)[:, 0]
    return (rows.max(axis=1) - got) / rows.std(axis=1)


def served_gaps(params, logits, dims, samples, *, width: int,
                served_max: int, control: bool = False):
    """``logits``: the family's reference forward, ``dims`` its sizes;
    ``samples``: (prompt, served tokens) pairs.  Each is run once,
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
                      jnp.asarray(pad), logits=logits, dims=dims,
                      control=control)
            out.append(np.asarray(g)[:len(served)])
    return out
