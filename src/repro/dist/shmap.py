"""``shard_map`` with the repo's calling convention: callers name the axes
they want manual (default: every mesh axis) and whether to check
replication, and get ``jax.shard_map(..., axis_names=..., check_vma=...)``.
"""

from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(f, mesh, in_specs, out_specs, manual_axes=None, check=False):
    """``shard_map`` manual over ``manual_axes`` (default: every mesh axis)."""
    manual = frozenset(manual_axes or mesh.axis_names)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check, axis_names=manual,
    )
