"""Production meshes + sharding rules for every (arch x shape) step.

Meshes (TPU v5e target):
  single-pod : (16, 16)      -> ("data", "model")      = 256 chips
  multi-pod  : (2, 16, 16)   -> ("pod", "data", "model") = 512 chips

The ``pod`` axis only ever carries batch/replica parallelism — inter-pod
DCN is the analogue of EPARA's inter-edge-server links, and EPARA's own S2
rule ("keep multi-GPU parallel services inside one server") maps to
keeping model parallelism inside a pod (DESIGN.md §4).

Sharding policy (baseline; hillclimbs recorded in EXPERIMENTS.md §Perf):
  weights    : 2D — rows on ``data`` (ZeRO/FSDP-style), cols on ``model``.
  batch      : ("pod","data") on the leading batch dim.
  activations: block-boundary constraint (batch, None, "model") so the
               remat-scan carries stay sharded (see EXPERIMENTS.md).
  caches     : batch on ``data`` when divisible, else sequence; kv-heads on
               ``model`` when divisible, else head_dim, else sequence.

Every spec passes through ``_pick``, which only shards divisible dims:
jit rejects uneven input shardings.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig, ShapeSpec

SINGLE_POD_SHAPE = (16, 16)
MULTI_POD_SHAPE = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """A mesh whose axes the compiler partitions (``Auto``):
    ``with_sharding_constraint`` and the rule-derived specs below assume
    that, where ``jax.make_mesh``'s default Explicit axes reject them."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= axis_size(mesh, n)
        return out
    return mesh.shape[name] if name in mesh.shape else 1


def batch_axes(mesh: Mesh):
    """The replica/batch mesh axes: ("pod","data") when a pod axis exists."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def _pick(mesh: Mesh, shape: Tuple[int, ...],
          prefs: Dict[Any, List[int]]) -> P:
    """Build a PartitionSpec: for each mesh axis (or axis tuple), assign the
    first preferred dim that is divisible by the axis size and not already
    taken.  Undividable/unclaimed dims stay replicated."""
    assignment: Dict[int, Any] = {}
    for axis, dims in prefs.items():
        size = axis_size(mesh, axis)
        if size <= 1:
            continue
        for d in dims:
            if d in assignment or d >= len(shape):
                continue
            if shape[d] % size == 0 and shape[d] > 0:
                assignment[d] = axis
                break
    spec = []
    for d in range(len(shape)):
        a = assignment.get(d)
        if isinstance(a, (tuple, list)):  # unwrap singleton axis tuples
            a = a[0] if len(a) == 1 else tuple(a)
        spec.append(a)
    return P(*spec)


# ---------------------------------------------------------------------------
# parameter sharding rules
# ---------------------------------------------------------------------------

_PARAM_RULES: List[Tuple[str, Dict[str, List[int]]]] = [
    # pattern (on the /-joined tree path), prefs by logical axis name.
    # dims are counted FROM THE RIGHT (negative) to be stack-agnostic:
    # a rule for (d, f) applies equally to layer-stacked (L, d, f).
    (r"embed/embedding$", {"model": [-2], "fsdp": [-1]}),
    (r"embed/unembed$", {"model": [-1], "fsdp": [-2]}),
    (r"(attn|self_attn|cross_attn)/w[qkv]$", {"model": [-1], "fsdp": [-2]}),
    (r"(attn|self_attn|cross_attn)/wqkv$", {"model": [-1], "fsdp": [-2]}),
    (r"(attn|self_attn|cross_attn)/bqkv$", {"model": [-1]}),
    (r"mlp/w_gateup$", {"model": [-1], "fsdp": [-2]}),
    (r"moe/w_gateup$", {"model": [-1], "fsdp": [-2]}),
    (r"(attn|self_attn|cross_attn)/b[qkv]$", {"model": [-1]}),
    (r"(attn|self_attn|cross_attn)/wo$", {"model": [-2], "fsdp": [-1]}),
    (r"mlp/w_(gate|up)$", {"model": [-1], "fsdp": [-2]}),
    (r"mlp/w_down$", {"model": [-2], "fsdp": [-1]}),
    (r"moe/router$", {"fsdp": [-2]}),
    (r"moe/w_(gate|up)$", {"model": [-1], "fsdp": [-2]}),
    (r"moe/w_down$", {"model": [-2], "fsdp": [-1]}),
    (r"in_proj$", {"model": [-1], "fsdp": [-2]}),
    (r"out_proj$", {"model": [-2], "fsdp": [-1]}),
    (r"conv_w$", {"model": [-1]}),
    (r"conv_b$", {"model": [-1]}),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def param_specs(mesh: Mesh, params_shape, *, fsdp: bool = True,
                expert_parallel: bool = False):
    """PartitionSpec tree for a params pytree (of ShapeDtypeStruct or
    arrays).  ``fsdp=False`` replicates the row dimension (pure-TP serving
    for small models — a §Perf hillclimb knob).  FSDP rows span
    ("pod","data") so the multi-pod mesh halves per-chip weight/optimizer
    state (grok-314b train fits 512 chips, see EXPERIMENTS.md §Dry-run)."""
    fsdp_axis = batch_axes(mesh) if fsdp else None
    rules = list(_PARAM_RULES)
    if expert_parallel:
        # expert weights (L, E, d, f): E on the model axis -> per-expert
        # GEMMs are expert-local and the dispatch becomes an all-to-all
        # instead of gathering the whole (E, tokens, d) operand (§Perf)
        rules = [(r"moe/w_(gate|up|gateup)$", {"model": [-3], "fsdp": [-2]}),
                 (r"moe/w_down$", {"model": [-3], "fsdp": [-1]})] + rules

    def spec_for(path, leaf):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        for pat, prefs in rules:
            if re.search(pat, pstr):
                axis_prefs: Dict[Any, List[int]] = {}
                for logical, dims in prefs.items():
                    axis = {"model": "model", "fsdp": fsdp_axis}[logical]
                    if axis is None:
                        continue
                    axis_prefs[axis] = [d % len(shape) for d in dims
                                        if -d <= len(shape)]
                return _pick(mesh, shape, axis_prefs)
        return P()  # norms, scalars, biases: replicate

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


# ---------------------------------------------------------------------------
# batch / cache sharding rules
# ---------------------------------------------------------------------------

def batch_specs(mesh: Mesh, batch_shape, *,
                replicate_batch: bool = False) -> Any:
    """tokens/labels (B, L) and embeddings (B, T, d): batch on the replica
    axes (falls back to replicated when B is not divisible, e.g. B=1).
    ``replicate_batch`` replicates everything — the 2D-TP serving mode
    (EXPERIMENTS.md §Perf: decode trades FSDP weight gathers for small
    activation psums)."""
    baxes = batch_axes(mesh)

    def spec_for(path, leaf):
        if replicate_batch:
            return P(*([None] * len(leaf.shape)))
        shape = tuple(leaf.shape)
        return _pick(mesh, shape, {baxes: [0]})

    return jax.tree_util.tree_map_with_path(spec_for, batch_shape)


def cache_specs(mesh: Mesh, cache_shape, *,
                replicate_batch: bool = False) -> Any:
    """Caches are (layers, B, ...) trees:
       attention k/v  (L, B, S, Hkv, hd) : B->data, Hkv|hd|S->model
       ssm conv       (L, B, k-1, ch)    : B->data, ch->model
       ssm state      (L, B, H, P, N)    : B->data, H|P->model
       cross k/v      (L, B, T, Hkv, hd) : same as attention.
    ``replicate_batch`` (2D-TP serving) moves the data axis from the batch
    dim to the SEQUENCE dim of attention caches (flash-decode-style
    sequence parallelism) and to state dims for SSM."""
    baxes = batch_axes(mesh)

    def spec_for(path, leaf):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        if pstr.endswith("len"):
            return P()
        if "conv" in pstr:
            prefs = {"model": [3]} if replicate_batch else                 {baxes: [1], "model": [3]}
            return _pick(mesh, shape, prefs)
        if "ssd" in pstr:
            prefs = {baxes: [2], "model": [3]} if replicate_batch else                 {baxes: [1], "model": [2, 3]}
            return _pick(mesh, shape, prefs)
        if shape and len(shape) == 5:      # attention caches
            prefs = {baxes: [2], "model": [3, 4]} if replicate_batch                 else {baxes: [1, 2], "model": [3, 4, 2]}
            return _pick(mesh, shape, prefs)
        return _pick(mesh, shape, {} if replicate_batch else {baxes: [1]})

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def arena_spec(mesh: Mesh, shape: Tuple[int, ...], *,
               pool: bool = False) -> P:
    """PartitionSpec for one serving-arena device buffer.  A page pool
    array (``pool=True``: values ``(layers, Hkv/G, pages, block_size,
    W)`` or an int8 pool's scale rows ``(layers, Hkv/G, rows, Ws)``,
    see ``kernels.paged_pool``) splits its head-group axis over
    ``model`` — the heads ``cache_specs`` splits for the dense cache —
    while the PAGE axis stays replicated (the block-table page
    indirection must resolve locally; model parallelism splits heads, not
    the pool).  Per-slot state leaves ``(layers, slots, ...)`` shard their
    head/channel axes when divisible."""
    nd = len(shape)
    if pool:
        prefs: Dict[Any, List[int]] = {"model": [1]}
    elif nd >= 4:
        prefs = {"model": [nd - 2, nd - 1]}
    elif nd >= 3:
        prefs = {"model": [nd - 1]}
    else:
        prefs = {}
    return _pick(mesh, tuple(shape), prefs)


def opt_state_specs(mesh: Mesh, opt_shape, params_spec) -> Any:
    """Optimizer state: moments follow the param sharding; scalars
    replicate; adafactor factored moments inherit the surviving dims."""

    def spec_for(path, leaf):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        # find the param this moment mirrors by suffix match
        flat_params = jax.tree_util.tree_flatten_with_path(params_spec)[0]
        for ppath, pspec in flat_params:
            ps = _path_str(ppath)
            if pstr.endswith(ps) or ps.endswith(pstr.split("/", 1)[-1]):
                if len(pspec) == len(shape):
                    # verify divisibility still holds
                    ok = all(s % axis_size(mesh, a) == 0
                             for s, a in zip(shape, tuple(pspec) +
                                             (None,) * len(shape))
                             if a is not None)
                    if ok:
                        return pspec
                break
        # fallback: re-derive by heuristics (shard biggest divisible dims)
        return _pick(mesh, shape, {"model": [len(shape) - 1],
                                   "data": [len(shape) - 2]})

    return jax.tree_util.tree_map_with_path(spec_for, opt_shape)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# activation constraint hook (keeps remat-scan carries sharded) — the hook
# itself lives in repro.models.sharding so models never import launch/.
# ---------------------------------------------------------------------------
from repro.models import sharding as _model_sharding  # noqa: E402


def set_activation_mesh(mesh: Optional[Mesh], *,
                        shard_model: bool = True) -> None:
    """``shard_model=False`` constrains only the batch dim: d_model-sharded
    carries save remat memory for 100B+ models but cost an extra
    all-gather/reduce pair per block for small ones (EXPERIMENTS §Perf)."""
    if mesh is None:
        _model_sharding.set_activation_fn(None)
        return

    baxes = batch_axes(mesh)

    def constrain(x):
        shape = tuple(x.shape)
        prefs = {baxes: [0]}
        if shard_model:
            prefs["model"] = [len(shape) - 1]
        spec = _pick(mesh, shape, prefs)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    _model_sharding.set_activation_fn(constrain)
