"""Phase 2: exact cosine re-ranking of phase-1 candidates (paper §2.2).

All vectors are unit-normalised at index build, so cosine == dot.  Because of
re-ranking, phase-1 *rank positions* are irrelevant -- only membership of the
gold documents in the candidate page matters (paper §3.1 note); the tests pin
this exactness property (``page >= n_docs`` => identical to brute force).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["normalize", "pairwise_sum", "exact_scores", "rerank_topk",
           "brute_force_topk", "EXACT"]

# Precision of every exact-cosine matmul (the rescore and the brute-force
# gold standard).  A TPU multiplies f32 in bf16 passes by default; HIGHEST
# keeps these scores fp32.  Candidate selection keeps the default.
EXACT = jax.lax.Precision.HIGHEST


def pairwise_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum the last axis with a fixed pairwise tree: zero-pad it to a power
    of two, then repeatedly add the upper half onto the lower.  The order
    of the additions is a function of the axis length alone, so a row sums
    to the same bits however many rows are computed with it -- ``jnp.sum``
    lets XLA choose the order per tensor shape."""
    n = x.shape[-1]
    p2 = 1 << max(n - 1, 0).bit_length()                 # next power of two
    if p2 != n:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, p2 - n),))
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def normalize(x: jnp.ndarray, eps: float = 1e-12) -> jnp.ndarray:
    """Unit rows.  The norm sums through :func:`pairwise_sum`, so a row
    normalizes to the same bits in a shard's block as in the whole table
    (the sharded build's parity with the single-device build rests on
    it)."""
    # max(., 0) is exact for a square, and keeps the CPU backend from
    # contracting the squares into the tree's adds as FMAs, which it does
    # for some rows of a block and not others
    sq = jnp.maximum(x * x, 0.0)
    norm = jnp.sqrt(pairwise_sum(sq))[..., None]
    return x / jnp.maximum(norm, eps)


def exact_scores(vectors: jnp.ndarray, ids: jnp.ndarray,
                 queries: jnp.ndarray) -> jnp.ndarray:
    """Exact cosines of the selected ids, (Q, k) from a (Q, k, n) einsum.

    Final reported scores always come from THIS shape, regardless of how the
    candidates were scored during selection -- the einsum's reduction
    blocking depends on the candidate-page shape, so recomputing at the
    fixed (Q, k, n) shape is what keeps single-device and doc-sharded
    search bit-identical (dist/shard_index.py merges through it too).
    """
    return jnp.einsum("qkn,qn->qk", vectors[ids], queries,
                      preferred_element_type=jnp.float32, precision=EXACT)


@partial(jax.jit, static_argnames=("k",))
def rerank_topk(
    vectors: jnp.ndarray,    # (d, n) unit-normalised index vectors
    cand_ids: jnp.ndarray,   # (Q, page) int32 phase-1 candidates
    queries: jnp.ndarray,    # (Q, n) unit-normalised queries
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact cosine top-k among the candidates -> (ids (Q,k), scores (Q,k))."""
    cand = vectors[cand_ids]                            # (Q, page, n)
    scores = jnp.einsum(
        "qpn,qn->qp", cand, queries, preferred_element_type=jnp.float32,
        precision=EXACT,
    )
    _, top_pos = jax.lax.top_k(scores, k)
    top_ids = jnp.take_along_axis(cand_ids, top_pos, axis=1)
    return top_ids, exact_scores(vectors, top_ids, queries)


@partial(jax.jit, static_argnames=("k", "block"))
def brute_force_topk(
    vectors: jnp.ndarray,   # (d, n)
    queries: jnp.ndarray,   # (Q, n)
    k: int,
    block: int = 8192,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The paper's naive baseline: one linear scan, O(nd) (gold standard).

    ``k`` clamps to the corpus size (same contract as ``VectorIndex.search``):
    without it, ``k > d`` rows would pad the result with ``(id 0, -inf)``
    junk that silently poisons any recall computed against it."""
    d, n = vectors.shape
    k = min(k, d)
    Q = queries.shape[0]
    pad = (-d) % block
    padded = jnp.pad(vectors, ((0, pad), (0, 0)))
    nb = padded.shape[0] // block
    blocks = padded.reshape(nb, block, n)

    def body(carry, inp):
        best_s, best_i = carry
        blk, base = inp
        s = jnp.matmul(queries, blk.T, precision=EXACT)  # (Q, block)
        ids = base + jnp.arange(block, dtype=jnp.int32)
        valid = ids < d
        s = jnp.where(valid[None, :], s, -jnp.inf)
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, (Q, block))], axis=1)
        ts, tp = jax.lax.top_k(cat_s, k)
        ti = jnp.take_along_axis(cat_i, tp, axis=1)
        return (ts, ti), None

    init = (
        jnp.full((Q, k), -jnp.inf, jnp.float32),
        jnp.zeros((Q, k), jnp.int32),
    )
    bases = (jnp.arange(nb) * block).astype(jnp.int32)
    (best_s, best_i), _ = jax.lax.scan(body, init, (blocks, bases))
    return best_i, best_s
