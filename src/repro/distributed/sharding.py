"""Logical-axis sharding: recipes mapping model-logical axes onto the mesh.

The paper switches between two dataflows per layer shape (Section IV-A); at
pod scale we switch between sharding *recipes* per workload shape:

  train / prefill   batch -> ("pod", "data"); sequence -> "model"
                    (Megatron-style sequence parallelism for the residual
                    stream; KV is gathered inside attention); params
                    2D-sharded (FSDP over "data" x TP over "model").
  decode            batch -> ("pod", "data"); KV-cache seq -> "model"
                    (split-KV decode: XLA partial-softmax-reduces over the
                    sharded cache axis); params TP-sharded over "model".
  decode_long       global_batch = 1: cache seq -> ("data", "model"),
                    recurrent-state heads -> "model".

Constraints are no-ops when no mesh is active (single-device tests) and skip
mesh axes that don't exist (e.g. "pod" on the single-pod mesh), so the same
model code runs everywhere.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

_STATE = threading.local()

# logical activation axis -> preferred mesh axes (tuples tried in order)
ACTIVATION_RULES = {
    "train": {
        "batch": ("pod", "data"),
        "seq": ("model",),
        "tokens_flat": ("pod", "data", "model"),
        "kv_seq": (),            # gathered for attention
        "experts": ("model",),
        "cache_seq": ("model",),
        "heads": (),
        "embed": (),
        "ffn": (),
    },
    "decode": {
        "batch": ("pod", "data"),
        "seq": (),
        "tokens_flat": ("pod", "data"),
        "kv_seq": ("model",),
        "experts": ("model",),
        "cache_seq": ("model",),
        "heads": (),
        "embed": (),
        "ffn": ("model",),
    },
    "decode_long": {
        "batch": (),
        "seq": (),
        "tokens_flat": (),
        "kv_seq": ("data", "model"),
        "experts": ("model",),
        "cache_seq": ("data", "model"),
        "heads": ("model",),
        "embed": (),
        "ffn": ("model",),
    },
}


def _rules() -> Optional[dict]:
    return getattr(_STATE, "rules", None)


@contextlib.contextmanager
def recipe(name: Optional[str]):
    """Activate an activation-sharding recipe ("train" / "decode" / ...)."""
    prev = _rules()
    _STATE.rules = ACTIVATION_RULES[name] if name else None
    try:
        yield
    finally:
        _STATE.rules = prev


def _mesh_axes():
    mesh = current_mesh()
    return None if mesh is None else mesh_axes_dict(mesh)


def mesh_axes_dict(mesh) -> dict:
    """{axis name: size} for a ``Mesh`` or an ``AbstractMesh``."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def current_mesh():
    """The abstract mesh active for this trace (set by ``jax.set_mesh``),
    usable as the ``mesh`` argument of ``shard_map``; None when no mesh is
    active."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the kernel wrappers
    produce outputs whose replication the tracer cannot prove
    (psum-combined partial contractions), and parity tests assert it
    instead."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def combine_matmul_partials(acc, axis_name: str):
    """Sum per-shard partial contractions (split-K tensor parallelism).

    Called inside a shard_map body.  The psum runs in the accumulator's own
    dtype, so int32 split-K partials combine exactly — a split-K kernel
    matmul stays bit-identical to the unsplit contraction."""
    return jax.lax.psum(acc, axis_name)


def combine_softmax_state(acc, m, l, axis_name: str, *, eps: float = 1e-37):
    """Merge per-shard online-softmax partial state into the global output.

    Called inside a shard_map body.  Each shard contributes flash-decoding
    state over its local KV split: ``m`` running max, ``l`` running
    denominator, ``acc`` the *unnormalized* weighted-value accumulator
    (broadcastable to ``acc``'s shape on the last dim).  A shard that saw
    only masked positions has m = -inf, l = 0 and contributes exactly 0.

        m_g = pmax(m);  out = psum(acc . e^{m-m_g}) / max(psum(l . e^{m-m_g}), eps)
    """
    m_all = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_all)
    l_all = jax.lax.psum(l * corr, axis_name)
    acc_all = jax.lax.psum(acc * corr, axis_name)
    return acc_all / jnp.maximum(l_all, eps)


def force_replicated(x):
    """with_sharding_constraint to fully-replicated (no-op without a mesh).

    Used to pin WHERE a reshard happens — e.g. gathering the int8-quantized
    form of a weight instead of its bf16 original (quantized FSDP gathers).
    """
    if _mesh_axes() is None:
        return x
    return jax.lax.with_sharding_constraint(x, P(*([None] * x.ndim)))


def logical_pspec(shape, logical_axes, recipe_name: str, mesh_axes: dict) -> P:
    """PartitionSpec for one array of ``shape`` whose dims carry the given
    logical axis names, resolved against a recipe + {mesh axis: size} dict.

    Mirrors :func:`shard` exactly (same silent-drop rules: a mesh axis is
    skipped when absent or when the dim is not divisible by the axis
    product), but is usable OUTSIDE a trace — the serving executor builds
    NamedShardings for params/caches from it."""
    return _resolve_spec(shape, logical_axes, ACTIVATION_RULES[recipe_name],
                         mesh_axes)


def shard(x, *logical_axes):
    """with_sharding_constraint by logical axis names (None = replicated).

    Axes are dropped silently when absent from the active mesh or when the
    dimension size is not divisible by the mesh-axis product.
    """
    rules = _rules()
    mesh = _mesh_axes()
    if rules is None or mesh is None:
        return x
    spec = _resolve_spec(x.shape, logical_axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def _resolve_spec(shape, logical_axes, rules, mesh_axes) -> P:
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    spec = []
    used = set()
    for dim, name in zip(shape, logical_axes):
        if name is None:
            spec.append(None)
            continue
        axes = tuple(a for a in rules.get(name, ())
                     if a in mesh_axes and a not in used)
        prod = int(np.prod([mesh_axes[a] for a in axes])) if axes else 1
        if axes and dim % prod == 0:
            spec.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            spec.append(None)
    return P(*spec)


# ---------------------------------------------------------------------------
# Parameter partition rules
# ---------------------------------------------------------------------------

_EXPERT_RE = re.compile(r"experts|expert_")
_SCAN_RE = re.compile(r"layers|blocks")


def _divisible(dim: int, mesh: dict, axis: str) -> bool:
    return axis in mesh and dim % mesh[axis] == 0


def param_spec(path: str, leaf, recipe_name: str, mesh: dict) -> P:
    """Partition spec for one parameter.

    train: 2D — last dim over "model", second-to-last over "data" (FSDP x TP).
    serve: 1D — last dim over "model" (weight-stationary TP).
    Expert tensors (..., E, d_in, d_out): E over "model", d_in over "data"
    (train only).  Scan-stacked leading layer dims stay replicated.  1D
    params (norm scales, biases) are replicated.
    """
    shape = leaf.shape
    ndim = len(shape)
    spec = [None] * ndim
    if ndim < 2:
        return P(*spec)
    is_expert = bool(_EXPERT_RE.search(path))
    if is_expert and ndim >= 3:
        e_ax = ndim - 3
        if _divisible(shape[e_ax], mesh, "model"):
            spec[e_ax] = "model"
        if recipe_name == "train" and _divisible(shape[-2], mesh, "data"):
            spec[-2] = "data"
        return P(*spec)
    if _divisible(shape[-1], mesh, "model"):
        spec[-1] = "model"
    if recipe_name == "train" and _divisible(shape[-2], mesh, "data"):
        spec[-2] = "data"
    return P(*spec)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def param_specs(params, recipe_name: str, mesh) -> "jax.tree_util.PyTreeDef":
    """PartitionSpec pytree matching ``params`` for the given recipe.
    ``mesh``: a concrete Mesh or a plain {axis name: size} dict."""
    mesh_axes = mesh if isinstance(mesh, dict) else mesh_axes_dict(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: param_spec(_path_str(p), l, recipe_name, mesh_axes), params)


def named_shardings(params, recipe_name: str, mesh):
    specs = param_specs(params, recipe_name, mesh)
    return jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
