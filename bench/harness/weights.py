"""Random weights from the seed, made by the benchmark itself.

The benchmark, not the program, draws the weights: the reference then
shares nothing the program made.  A family's ``layout(dims)`` gives every
leaf as ``(shape, kind)``, nested like the program's parameter tree; they
are drawn on the device in one jitted call, in bf16 (the type they are
served in).  Under a mesh they are born with the shardings the program's
serving placement gives them, so no device holds the whole model.

Matrices and the embedding are N(0, 0.02^2), the initializer range of
the Llama and Qwen families: with it every layer adds to the residual
stream about as much as the token's embedding holds, so the next token
depends on attention over the context.  (With a unit-variance embedding
and 1/fan_in matrices a random tied model mostly repeats its input token,
and a check of its tokens cannot tell a broken attention from a sound
one.)  Norm gains are 1 + 0.1 N(0, 1) and q/k/v biases 0.02 N(0, 1), so
both take part in the check instead of sitting at identity values.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def shapes(layout: Dict[str, Any]):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s[0], jnp.bfloat16), layout,
        is_leaf=_is_spec)


def seed_key(seed: int):
    """A key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, spec: Tuple) -> jax.Array:
    shape, kind = spec
    z = jax.random.normal(key, shape, jnp.float32)
    z = 1.0 + 0.1 * z if kind == "norm" else INIT_STD * z
    return z.astype(jnp.bfloat16)


def init_weights(layout: Dict[str, Any], seed: int, shardings=None):
    """The weights of ``layout`` for ``seed``, made on the device in one
    jitted call; ``shardings`` (a tree like ``layout``) places them on a
    mesh."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_spec)

    def make(key):
        return jax.tree.unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), spec)
            for i, spec in enumerate(leaves)])

    return jax.jit(make, out_shardings=shardings)(seed_key(seed))
