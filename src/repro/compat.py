"""The one ``shard_map`` spelling this repo uses."""
from __future__ import annotations

from typing import Any

import jax

__all__ = ["shard_map"]


def shard_map(f, mesh, in_specs, out_specs, axis_names: set[str] | None = None):
    """``jax.shard_map`` with the replication (vma) check off.

    ``axis_names`` names the manual mesh axes (all of them when None) and
    must be a subset of the mesh's. The check is off because our workers
    derive varying values from ``axis_index``, which the static analysis
    cannot see through.
    """
    if axis_names is not None and not set(axis_names) <= set(mesh.axis_names):
        raise ValueError(
            f"axis_names {sorted(axis_names)} not a subset of mesh axes "
            f"{mesh.axis_names}")
    kwargs: dict[str, Any] = {"check_vma": False}
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
