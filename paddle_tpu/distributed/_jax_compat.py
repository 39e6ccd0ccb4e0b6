"""The jax spellings the distributed stack is written against, in one
place: ``jax.shard_map``, ``jax.set_mesh``, ``lax.axis_size`` (the
installed jax 0.9.0 surface; no older version is supported)."""
from __future__ import annotations

import jax
from jax import lax

__all__ = ["shard_map", "use_mesh", "axis_size"]

use_mesh = jax.set_mesh
axis_size = lax.axis_size


def shard_map(fn, mesh, in_specs, out_specs, axis_names=None,
              check_vma=False):
    """``jax.shard_map`` with this tree's defaults: ``check_vma`` off,
    ``axis_names`` (the axes manual inside the body) accepted as any
    iterable, ``None`` meaning every mesh axis."""
    kwargs = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)
