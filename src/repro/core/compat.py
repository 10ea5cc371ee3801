"""The two sharding entry points every executor and mesh builder shares.

``shard_map`` fixes ``check_vma=False`` (the executors thread per-rank
state whose varying-manual-axes typing the checker cannot follow);
``make_mesh`` builds meshes with explicit ``Auto`` axis types so
sharding propagation behaves the same on every caller's mesh.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with ``check_vma`` off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(shape, axes):
    """``jax.make_mesh`` over the first ``prod(shape)`` devices, every
    axis ``AxisType.Auto``."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
