"""Paper-faithful inverted index over feature tokens.

This is the literal Lucene/Elasticsearch retrieval algorithm (paper §2.3,
"high-pass filtering" complexity analysis) re-expressed with fixed shapes so
it jits:

* **build** -- for every code column the documents are sorted by bucket value;
  a "posting list" for token ``(column j, bucket b)`` is then the contiguous
  range of the sorted order whose codes equal ``b``.  Finding it is a binary
  search, ``O(log j)``, exactly the paper's term-dictionary lookup.
* **df table** -- a column holds only ``2 * max_abs_bucket + 1`` legal
  codes, so every token's document frequency is fixed by the index alone:
  :func:`build_df_table` counts each (column, code) straight off the code
  matrix, no posting list needed, and a query reads its tokens' df from
  that table (:func:`table_df`).
* **score** -- for every surviving query token we fetch its posting range and
  scatter-add the token weight into a dense score accumulator
  (``jax.ops.segment_sum`` = the hash-map accumulator of the paper), then
  take the top-``page`` candidates.

Shapes are static: per-column gathers read a fixed window of
``max_postings`` entries (masked beyond the true range).  ``max_postings >=
n_docs`` makes the engine exact; smaller values trade recall for speed the
same way a real engine's early-termination does.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Postings", "build_postings", "lookup", "idf_weights",
           "score_postings", "code_df", "df_lookup", "DF_TABLE_MAX_BYTES",
           "build_df_table", "table_df"]

# Largest df table kept per shard.  Only an encoder with int16 or int32
# codes can pass it (800 columns of int8 codes take at most 0.8 MiB); its
# shards keep answering df with the binary searches of df_lookup.
DF_TABLE_MAX_BYTES = 64 << 20


class Postings(NamedTuple):
    """Inverted index: per column, doc ids sorted by their bucket code."""

    post_docs: jnp.ndarray   # (C, d) int32 -- doc ids, sorted by code per column
    post_codes: jnp.ndarray  # (C, d) intN  -- the sorted codes themselves
    n_docs: int


def build_postings(codes: jnp.ndarray) -> Postings:
    """codes: (d, C) -> Postings.  Pure JAX; runs under jit.

    One stable sort of every column with its doc ids riding along as the
    payload: the sort returns both posting tables directly, in their
    (C, d) layout, with no gather and no transposed copies."""
    d, _ = codes.shape
    cols = codes.T                                           # (C, d)
    ids = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    post_codes, post_docs = jax.lax.sort(
        (cols, ids), dimension=1, num_keys=1, is_stable=True)
    return Postings(post_docs=post_docs, post_codes=post_codes, n_docs=d)


def _searchsorted_row(row: jnp.ndarray, value: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    lo = jnp.searchsorted(row, value, side="left")
    hi = jnp.searchsorted(row, value, side="right")
    return lo, hi


def lookup(postings: Postings, qcodes: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Binary-search every query token's posting range.

    qcodes: (C,) -> (lo, hi) each (C,).  ``hi - lo`` is the document frequency
    of the token (paper's ``l``).
    """
    lo, hi = jax.vmap(_searchsorted_row)(postings.post_codes, qcodes)
    return lo, hi


def code_df(codes: jnp.ndarray, qcodes: jnp.ndarray) -> jnp.ndarray:
    """Per-token document frequency against a raw ``(d, C)`` code matrix.

    The segment-side analogue of :func:`lookup`'s ``hi - lo``: append
    segments (incremental ingest, :mod:`repro.dist.shard_index`) carry no
    posting lists, so their df contribution is a direct per-column bucket
    equality count.  Sentinel-coded rows (empty slots, tombstones) can never
    equal a legal query code and contribute zero automatically.

    qcodes: (Q, C) -> (Q, C) int32 counts.
    """
    return jnp.sum(qcodes[:, None, :] == codes[None, :, :], axis=1,
                   dtype=jnp.int32)


def df_lookup(postings: Postings, qcodes: jnp.ndarray) -> jnp.ndarray:
    """Batched document frequencies straight off the posting lists.

    qcodes: (Q, C) -> (Q, C) int32; per token the count is ``hi - lo`` of
    :func:`lookup`'s range.  Integer-exact and therefore bit-identical to
    :func:`code_df` over the same code matrix (tombstones and padding carry
    the sentinel, which sorts past every legal range), but O(log d) per
    token instead of O(d).  The search path reads df from the table
    :func:`build_df_table` counts, and calls this lookup per query only
    where the code range is too wide for a table (:func:`table_df`).
    """
    lo, hi = jax.vmap(lambda c: lookup(postings, c))(qcodes)
    return (hi - lo).astype(jnp.int32)


def _table_codes(max_abs_bucket: int, sentinel: int) -> np.ndarray:
    """The code each entry of a df table holds the df of, in entry order:
    the ``2 * max_abs_bucket + 1`` legal codes, then the sentinel (unless it
    is one of them)."""
    return np.union1d(np.arange(-max_abs_bucket, max_abs_bucket + 1),
                      [sentinel])


def build_df_table(codes: jnp.ndarray, max_abs_bucket: int,
                   sentinel: int) -> jnp.ndarray:
    """The document frequency of every code a column's rows can carry.

    codes: (d, C) -> (C, W) int32: entry ``[c, i]`` counts the rows whose
    column ``c`` holds code ``_table_codes(max_abs_bucket, sentinel)[i]``
    -- the legal codes, then the sentinel (the padded and tombstoned rows)
    -- so a table read is integer-identical to :func:`df_lookup` over the
    same codes' postings and to :func:`code_df`.  One compare-and-sum pass
    over the codes per table entry; no posting list is read or built.
    Where (C, W) int32 would pass :data:`DF_TABLE_MAX_BYTES` (int16 or
    int32 codes) the table is empty, (C, 0), and :func:`table_df` keeps
    the lookup.
    """
    C = codes.shape[1]
    vals = _table_codes(max_abs_bucket, sentinel)
    if C * vals.size * 4 > DF_TABLE_MAX_BYTES:
        return jnp.zeros((C, 0), jnp.int32)
    vals = jnp.asarray(vals, codes.dtype)
    counts = jax.lax.map(
        lambda v: jnp.sum(codes == v, axis=0, dtype=jnp.int32), vals)
    return counts.T                                          # (C, W)


def table_df(table: jnp.ndarray, postings: Optional[Postings],
             qcodes: jnp.ndarray, max_abs_bucket: int,
             sentinel: int) -> jnp.ndarray:
    """Per-token document frequency, (Q, C) int32, read from ``table``
    (:func:`build_df_table` of the rows ``postings`` index).

    The read compares each query code with the W codes the table holds and
    sums the one entry that matches: on a TPU a dense compare-and-select
    over (W, Q, C) that the vector unit does in one pass, where a gather of
    the Q * C entries is a slow serial op.  Equal to ``df_lookup(postings,
    qcodes)`` for every code: a code the table holds no entry for matches
    none and reads 0, and no row carries one (a row's code is a legal
    bucket of the same encoder or the sentinel).  Only an empty table (code
    range too wide) reads ``postings``, through :func:`df_lookup` itself;
    with a table they may be ``None``.
    """
    if not table.shape[-1]:
        return df_lookup(postings, qcodes)
    vals = jnp.asarray(_table_codes(max_abs_bucket, sentinel), jnp.int32)
    hit = qcodes.astype(jnp.int32)[None] == vals[:, None, None]  # (W, Q, C)
    return jnp.sum(jnp.where(hit, table.T[:, None, :], 0), axis=0,
                   dtype=jnp.int32)


def idf_weights(df: jnp.ndarray, n_docs: int) -> jnp.ndarray:
    """Lucene-style idf:  ln(1 + (N - df + 0.5) / (df + 0.5))."""
    df = df.astype(jnp.float32)
    return jnp.log1p((n_docs - df + 0.5) / (df + 0.5))


@partial(jax.jit, static_argnames=("max_postings", "weighting"))
def score_postings(
    postings: Postings,
    qcodes: jnp.ndarray,       # (C,) query bucket codes
    col_mask: jnp.ndarray,     # (C,) bool -- surviving query tokens
    max_postings: int,
    weighting: str = "idf",    # "idf" | "count"
    col_weights: Optional[jnp.ndarray] = None,  # optional extra per-column weight
) -> jnp.ndarray:
    """Dense scores (d,) via posting-list traversal + scatter-add."""
    C, d = postings.post_codes.shape
    lo, hi = lookup(postings, qcodes)
    df = hi - lo
    if weighting == "idf":
        w = idf_weights(df, postings.n_docs)
    elif weighting == "count":
        w = jnp.ones((C,), jnp.float32)
    else:
        raise ValueError(f"unknown weighting {weighting!r}")
    if col_weights is not None:
        w = w * col_weights
    w = jnp.where(col_mask, w, 0.0)

    # fixed-size posting window per column (masked beyond the true range)
    pos = lo[:, None] + jnp.arange(max_postings)[None, :]          # (C, L)
    valid = pos < hi[:, None]
    pos = jnp.minimum(pos, d - 1)
    docs = jnp.take_along_axis(postings.post_docs, pos, axis=1)    # (C, L)
    contrib = jnp.where(valid, w[:, None], 0.0)                    # (C, L)
    scores = jax.ops.segment_sum(
        contrib.reshape(-1), docs.reshape(-1).astype(jnp.int32), num_segments=d
    )
    return scores


def score_postings_batch(
    postings: Postings,
    qcodes: jnp.ndarray,      # (Q, C)
    col_mask: jnp.ndarray,    # (Q, C)
    max_postings: int,
    weighting: str = "idf",
    col_weights: Optional[jnp.ndarray] = None,  # (Q, C) or None
) -> jnp.ndarray:
    """Batched scoring: (Q, d)."""
    fn = lambda qc, cm, cw: score_postings(
        postings, qc, cm, max_postings, weighting, cw
    )
    if col_weights is None:
        return jax.vmap(lambda qc, cm: fn(qc, cm, None))(qcodes, col_mask)
    return jax.vmap(fn)(qcodes, col_mask, col_weights)
