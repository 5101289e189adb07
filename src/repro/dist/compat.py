"""The jax sharding / SPMD spellings every mesh and shard_map in the tree
goes through (jax 0.9): ``jax.make_mesh`` with explicit Auto axis types,
``jax.set_mesh`` as the ambient-mesh context, and ``jax.shard_map`` with
replication checking off by default, matching the repo's explicit-spec
style. A mesh axis is *manual* inside the mapped body unless left out of
``axis_names``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Set

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with explicit-Auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
        devices=devices,
    )


def set_mesh(mesh):
    """Ambient-mesh context (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


def shard_map(f, mesh, in_specs, out_specs, *, check: bool = False,
              axis_names: Optional[Set[str]] = None):
    """``jax.shard_map``; ``check`` maps to ``check_vma``. ``axis_names``
    is the set of manual axes; axes outside it stay automatic
    (XLA-sharded inside the body)."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)
