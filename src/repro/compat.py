"""Thin wrappers over the jax sharding APIs, so every call site builds
meshes and ``shard_map`` programs the same way."""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with explicit Auto axis types."""
    kwargs = {"axis_types": (jax.sharding.AxisType.Auto,) * len(axis_names)}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without varying-manual-axes checking."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
