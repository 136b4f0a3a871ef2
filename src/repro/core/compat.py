"""``shard_map`` and ``make_mesh`` as every internal call site uses them.

The repo targets ``jax.shard_map`` (keyword ``mesh``, ``check_vma``,
partial-manual regions via ``axis_names``) and meshes whose axes are all
``AxisType.Auto``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: Optional[bool] = None):
    """``jax.shard_map`` facade.

    axis_names: axes MANUAL inside ``f`` (None = all mesh axes)."""
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def make_mesh(shape: Sequence[int], names: Sequence[str]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with all axes auto."""
    names = tuple(names)
    return jax.make_mesh(tuple(shape), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))
