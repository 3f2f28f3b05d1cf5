"""Mesh and ``shard_map`` with this repo's one policy for each.

``make_mesh`` builds every mesh with ``Auto`` axis types, and
``shard_map`` turns the replication check off (the sharded searches mix
collectives with per-shard reductions, which the static checker rejects).
"""
from __future__ import annotations

from typing import Any, Callable

import jax


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
    )


def shard_map(
    f: Callable, *, mesh: jax.sharding.Mesh, in_specs: Any, out_specs: Any
) -> Callable:
    """``jax.shard_map`` with the replication check off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
