"""The mesh / sharding surface of JAX, pinned to the installed 0.9 series.

These are the JAX symbols that moved or changed signature in past
releases: ``shard_map`` (once ``jax.experimental.shard_map`` with a
``check_rep`` flag), ``make_mesh`` and ``AxisType`` (axis types),
``AbstractMesh`` (constructor signature) and ``lax.axis_size``.  Call sites
take them from here, so the next such move is an edit to this one file.

POLICY: none of these symbols may be referenced outside this module
(machine-enforced by rule RPR001 of ``python -m repro.analysis``, which
reads the ``COMPAT_SENSITIVE_*`` registry below).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax

# Stable re-exports, so call sites can take everything mesh/sharding-related
# from one place.
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: F401

# ---------------------------------------------------------------------------
# The compat-sensitivity registry: the symbols above, which must never be
# spelled at call sites.  repro.analysis rule RPR001 reads these, so adding
# a wrapper and banning direct use are the same edit.
# ---------------------------------------------------------------------------

# Dotted attribute paths.
COMPAT_SENSITIVE_ATTRS = frozenset(
    {
        "jax.shard_map",
        "jax.experimental.shard_map.shard_map",
        "jax.sharding.AxisType",
        "jax.sharding.AbstractMesh",
        "jax.make_mesh",
        "jax.lax.axis_size",
    }
)

# Modules that must not be imported (their contents moved).
COMPAT_SENSITIVE_MODULES = frozenset({"jax.experimental.shard_map"})

# Names that must not be from-imported out of any jax.* module.
COMPAT_SENSITIVE_NAMES = frozenset(
    {"shard_map", "AxisType", "AbstractMesh", "make_mesh", "axis_size"}
)

AxisType = jax.sharding.AxisType
axis_size = jax.lax.axis_size


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: Optional[bool] = None):
    """``jax.shard_map``; ``check_vma=None`` keeps JAX's default."""
    kwargs: dict = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    axis_types: Optional[Sequence[Any]] = None,
    devices=None,
) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` unless ``axis_types`` says
    otherwise (GSPMD decides placement, which the sharding rules assume)."""
    axis_names = tuple(axis_names)
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_names)
    kwargs: dict = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(axis_shapes), axis_names, axis_types=tuple(axis_types), **kwargs
    )


def abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """Device-free mesh for sharding resolution."""
    axis_shapes = tuple(axis_shapes)
    axis_names = tuple(axis_names)
    if len(axis_shapes) != len(axis_names):
        raise ValueError(
            f"axis_shapes {axis_shapes} and axis_names {axis_names} disagree"
        )
    return jax.sharding.AbstractMesh(axis_shapes, axis_names)
