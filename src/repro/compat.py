"""JAX API spellings shared across the codebase, for the pinned JAX 0.9.0.

``make_mesh(axis_shapes, axis_names)``
    ``jax.make_mesh`` with every axis typed ``AxisType.Auto``: the repo
    shards through GSPMD propagation and ``with_sharding_constraint`` and
    never uses ``Explicit`` sharding.

``shard_map(f, mesh=..., in_specs=..., out_specs=...)``
    ``jax.shard_map``.
"""
from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["make_mesh", "shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs, **kwargs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(axis_shapes, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))
