"""Seeded data for a run, made on the device.

The corpus is a topic mixture (``chip_smoke.py``'s ``Mixture``: each row is
one of ``n_topics`` Gaussian topic directions plus Gaussian noise of
standard deviation ``noise``), so cosine neighbourhoods carry structure the
way LSA vectors do.  Rows are made in blocks of ``BLOCK``, each block from
its own key folded out of the seed, so a row's value depends on the seed and
its global index alone: the first 1,045,376 rows of the 4-shard corpus are
the 1-shard corpus of the same seed, and the reference can make the same
rows again on any layout.  Each chip makes its own shard's rows.

The query pool is drawn from the same mixture (same topics) under another
fold of the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

BLOCK = 128
_TOPICS, _DOCS, _QUERIES = 0, 1, 2


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _topics(key, n_topics, n_features):
    return jax.random.normal(jax.random.fold_in(key, _TOPICS),
                             (n_topics, n_features), jnp.float32)


def _blocks(key, stream, block_ids, topics, noise):
    """Rows of the given global blocks -> (len(block_ids), BLOCK, F)."""
    base = jax.random.fold_in(key, stream)

    def one(b):
        kt, kn = jax.random.split(jax.random.fold_in(base, b))
        t = jax.random.randint(kt, (BLOCK,), 0, topics.shape[0])
        eps = jax.random.normal(kn, (BLOCK, topics.shape[1]), jnp.float32)
        return topics[t] + noise * eps

    return jax.vmap(one)(block_ids)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "n_docs", "n_features", "n_topics", "noise"))
def _corpus(key, *, mesh, n_docs, n_features, n_topics, noise):
    from jax import shard_map

    axis = mesh.axis_names[0]
    n_blocks = n_docs // BLOCK
    ids = jax.lax.with_sharding_constraint(
        jnp.arange(n_blocks, dtype=jnp.uint32),
        NamedSharding(mesh, P(axis)))

    def local(b):
        return _blocks(key, _DOCS, b, _topics(key, n_topics, n_features),
                       noise)

    rows = shard_map(local, mesh=mesh, in_specs=P(axis),
                     out_specs=P(axis), check_vma=False)(ids)
    return rows.reshape(n_docs, n_features)


def corpus(seed: int, n_docs: int, n_features: int, n_topics: int,
           noise: float, mesh) -> jax.Array:
    """(n_docs, n_features) float32 rows, sharded along ``mesh``'s first
    axis (one contiguous doc shard per device)."""
    n_dev = mesh.devices.size
    if n_docs % (BLOCK * n_dev):
        raise ValueError(
            f"n_docs {n_docs} must be a multiple of {BLOCK} x {n_dev} shards")
    return _corpus(root_key(seed), mesh=mesh, n_docs=n_docs,
                   n_features=n_features, n_topics=n_topics,
                   noise=float(noise))


@functools.partial(jax.jit, static_argnames=(
    "n", "n_features", "n_topics", "noise"))
def _pool(key, *, n, n_features, n_topics, noise):
    ids = jnp.arange(-(-n // BLOCK), dtype=jnp.uint32)
    rows = _blocks(key, _QUERIES, ids, _topics(key, n_topics, n_features),
                   noise)
    return rows.reshape(-1, n_features)[:n]


def query_pool(seed: int, n: int, n_features: int, n_topics: int,
               noise: float) -> np.ndarray:
    """(n, n_features) float32 query vectors on the host."""
    return np.asarray(_pool(root_key(seed), n=n, n_features=n_features,
                            n_topics=n_topics, noise=float(noise)))
