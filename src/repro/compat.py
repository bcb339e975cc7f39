"""One home for the JAX calls whose spelling has moved between releases.

Written against the installed JAX (0.9), where ``jax.shard_map``,
``jax.sharding.AxisType`` and ``jax.enable_x64`` all exist. Call sites
go through this module, so a future rename is fixed here once.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def enable_x64(enabled: bool = True):
    """Context manager enabling 64-bit types (``pred_mode='packed'``)."""
    return jax.enable_x64(enabled)


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a flat dict (empty when the
    backend reports nothing)."""
    return compiled.cost_analysis() or {}
