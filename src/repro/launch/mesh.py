"""Production meshes (brief-mandated): 16x16 single pod, 2x16x16 multi-pod.

A FUNCTION, not a module constant -- importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init; tests and
benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_shard_mesh"]


def _auto_axes(ndim: int) -> tuple:
    """``Auto`` axis types for an ``ndim``-axis mesh.  ``jax.make_mesh``
    defaults to ``Explicit`` axes, under which the gathers and scatters of
    the sharded index raise ``ShardingTypeError``; every mesh this repo
    builds is GSPMD-propagated (``Auto``) instead."""
    return (AxisType.Auto,) * ndim


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto_axes(len(shape)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (CPU) devices exist -- for tests."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"), _auto_axes(2),
                         devices=jax.devices()[: data * model])


def make_shard_mesh(n_shards: int, n_replicas: int = 1):
    """Mesh for doc-sharded search: 1-D ``data`` (one doc-shard per device),
    or 2-D ``(data, replica)`` when ``n_replicas > 1`` (each doc-shard
    replicated across the ``replica`` axis, ES replica shards).

    Search has no tensor-parallel dimension -- every shard runs the whole
    two-phase pipeline over its own document range -- so the axes are pure
    serving axes: ``data`` partitions the corpus, ``replica`` multiplies
    QPS.  Use ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to fan
    a CPU host out into N virtual shard hosts.
    """
    need = n_shards * n_replicas
    devs = jax.devices()
    if need > len(devs):
        raise ValueError(
            f"{n_shards} shards x {n_replicas} replicas need {need} devices "
            f"but only {len(devs)} exist; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} "
            "before the first jax import")
    if n_replicas == 1:                      # keep the PR-1 1-D mesh contract
        return jax.make_mesh((n_shards,), ("data",), _auto_axes(1),
                             devices=devs[:need])
    return jax.make_mesh((n_shards, n_replicas), ("data", "replica"),
                         _auto_axes(2), devices=devs[:need])
