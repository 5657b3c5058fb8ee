"""Logical-axis sharding: rules, constraint helper, param shardings.

Tensors (params and activations) carry *logical* axis names
("batch", "heads", "d_ff", ...).  A rule table maps logical names to mesh
axes ("pod", "data", "model").  Resolution is shape-aware: if a dimension is
not divisible by the mapped mesh-axis size, the mapping falls back to
replication for that dimension (recorded, surfaced in the dry-run report) —
this is what makes awkward head counts / batch=1 long-context shapes lower
cleanly instead of erroring.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.compat import Mesh, NamedSharding, PartitionSpec

AxisName = Union[str, Tuple[str, ...]]

# Logical axis -> mesh axis (or tuple of mesh axes).
DEFAULT_RULES: Dict[str, AxisName] = {
    "batch": ("pod", "data"),
    "seq_sp": "model",    # sequence-parallel residual stream
    "kv_seq": "data",     # long-context KV-cache sequence sharding
    "heads": "model",
    "kv_heads": "model",
    "d_ff": "model",
    "vocab": "model",
    "experts": "model",
    "inner": "model",     # xlstm / mamba inner projection dim
    "mamba_heads": "model",
    "state": None,
    # ZeRO-1: optimizer moments additionally shard a replicated dim over data.
    "zero1": ("pod", "data"),
}


def zero1_axes(param_axes: Any, param_shapes: Any, divisor: int) -> Any:
    """Optimizer-moment axes: like the param, plus one unsharded dim sharded
    over the data axes (ZeRO-1).  Shape-aware: picks the first dim divisible
    by the data-parallel degree (skips e.g. 95-layer stack dims)."""

    def is_axes_leaf(x):
        return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)

    flat_shapes, treedef = jax.tree.flatten(param_shapes)
    flat_axes = treedef.flatten_up_to(param_axes)

    out = []
    for sds, axes in zip(flat_shapes, flat_axes):
        best = None
        for i, (dim, a) in enumerate(zip(sds.shape, axes)):
            if a is None and dim % divisor == 0:
                best = i
                break
        if best is None:
            out.append(axes)
        else:
            new = list(axes)
            new[best] = "zero1"
            out.append(tuple(new))
    return treedef.unflatten(out)


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[Dict[str, AxisName]] = None
    fallbacks: list = []
    suspended: bool = False


_CTX = _Ctx()


@contextlib.contextmanager
def no_constraints():
    """Suspend logical_constraint (e.g. inside shard_map bodies, where mesh
    axes are manual and with_sharding_constraint is disallowed)."""
    prev = _CTX.suspended
    _CTX.suspended = True
    try:
        yield
    finally:
        _CTX.suspended = prev


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: Optional[Dict[str, AxisName]] = None):
    """Activate a mesh + rule table for logical_constraint resolution."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    merged = dict(DEFAULT_RULES, **(rules or {}))
    # JSON-sourced overrides arrive as lists; normalize to tuples.
    _CTX.rules = {k: tuple(v) if isinstance(v, list) else v for k, v in merged.items()}
    _CTX.fallbacks = []
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def fallback_log() -> list:
    """Divisibility fallbacks recorded during the last use_rules scope."""
    return list(_CTX.fallbacks)


def _mesh_axis_size(mesh: Mesh, axis: AxisName) -> int:
    if isinstance(axis, tuple):
        return int(np.prod([_mesh_axis_size(mesh, a) for a in axis]))
    return mesh.shape[axis]


def _filter_axis(mesh: Mesh, axis: AxisName) -> Optional[AxisName]:
    """Drop mesh axes that don't exist in this mesh (e.g. no 'pod')."""
    if isinstance(axis, tuple):
        kept = tuple(a for a in axis if a in mesh.shape)
        return kept if kept else None
    return axis if axis in mesh.shape else None


def resolve_spec(
    shape: Sequence[int],
    axes: Sequence[Optional[str]],
    mesh: Optional[Mesh] = None,
    rules: Optional[Dict[str, AxisName]] = None,
) -> PartitionSpec:
    """Logical axes -> PartitionSpec with shape-aware divisibility fallback."""
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules or DEFAULT_RULES
    assert mesh is not None, "resolve_spec needs a mesh (use use_rules)"
    parts = []
    used: set = set()
    for dim, name in zip(shape, axes):
        if name is None:
            parts.append(None)
            continue
        mapped = rules.get(name)
        if mapped is None:
            parts.append(None)
            continue
        mapped = _filter_axis(mesh, mapped)
        if mapped is None:
            parts.append(None)
            continue
        # A mesh axis may appear at most once in a spec.
        flat = mapped if isinstance(mapped, tuple) else (mapped,)
        if any(a in used for a in flat):
            parts.append(None)
            continue
        size = _mesh_axis_size(mesh, mapped)
        if dim % size != 0:
            # Try a prefix of the axis tuple (e.g. ("pod","data") -> ("pod",)).
            ok = None
            if isinstance(mapped, tuple):
                for cut in range(len(mapped) - 1, 0, -1):
                    sub = mapped[:cut]
                    if dim % _mesh_axis_size(mesh, sub) == 0:
                        ok = sub
                        break
            if ok is None:
                _CTX.fallbacks.append((tuple(shape), name, mapped, dim, size))
                parts.append(None)
                continue
            mapped = ok
            flat = mapped if isinstance(mapped, tuple) else (mapped,)
        used.update(flat)
        parts.append(mapped)
    while parts and parts[-1] is None:
        parts.pop()
    return PartitionSpec(*parts)


def logical_constraint(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint by logical names; identity with no mesh."""
    if _CTX.mesh is None or _CTX.suspended:
        return x
    spec = resolve_spec(x.shape, axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


def named_sharding(
    mesh: Mesh,
    shape: Sequence[int],
    axes: Sequence[Optional[str]],
    rules: Optional[Dict[str, AxisName]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(shape, axes, mesh, rules))


def tree_shardings(
    mesh: Mesh,
    shapes: Any,     # pytree of arrays or ShapeDtypeStruct
    axes: Any,       # matching pytree whose leaves are tuples of logical names
    rules: Optional[Dict[str, AxisName]] = None,
) -> Any:
    """Build a NamedSharding pytree for pjit in/out_shardings."""

    def leaf(s, a):
        return named_sharding(mesh, s.shape, a, rules)

    # tree_map flattens up to `shapes`' leaves, so the tuple-of-names leaves
    # of `axes` pass through intact.
    return jax.tree.map(leaf, shapes, axes)
