"""jax API shims: one spelling of ``shard_map`` and of mesh construction
for the whole codebase (the installed jax is 0.9)."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types on every axis."""
    kw = {}
    if devices is not None:
        kw["devices"] = devices
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names), **kw)
