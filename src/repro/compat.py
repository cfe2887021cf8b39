"""The jax API spellings this repo targets (jax 0.9).

Every shard_map and mesh in the repo goes through these three wrappers,
so replication checking and axis types are set in one place.
"""
from __future__ import annotations

import jax
from jax import lax


def axis_size(axis_name) -> int:
    """Static named-axis size inside shard_map."""
    return lax.axis_size(axis_name)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types for every axis."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))
