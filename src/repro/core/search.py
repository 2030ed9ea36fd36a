"""Public two-phase search API (paper §2.2): VectorIndex.

    idx = VectorIndex.build(vectors, encoder=RoundingEncoder(2))
    ids, sims = idx.search(queries, k=10, page=320, trim=TrimFilter(0.05))

Phase 1 retrieves ``page`` candidates with one of the engines

* ``postings``   -- paper-faithful inverted index (:mod:`repro.core.postings`)
* ``codes``      -- TPU-native code-match streaming (:mod:`repro.core.codes`)
* ``onehot``     -- MXU matmul over the one-hot token vocabulary
* ``codes_pallas`` -- the code_match Pallas kernel (full score matrix)
* ``fused``      -- fused Pallas kernel: code-match scoring + running
  top-``page`` in one pass, no (Q, n_docs) score matrix
  (:mod:`repro.kernels.fused_phase1`)
* ``fused_int8`` -- the fused kernel over the int8 per-row quantized copy
  of the dense table (:mod:`repro.core.quantize`, derived lazily and
  cached per index instance) -- phase-1 selection only

and phase 2 re-ranks them by exact cosine (:mod:`repro.core.rerank`) --
for every engine, including the quantized one, so reported scores are
always exact fp32.
Filtering (trim/best) is query-side by default -- choosable per request, the
paper's §5 recommendation -- with optional index-side ``best`` at build time.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .codes import score_codes, score_onehot
from .encoding import Encoder, RoundingEncoder
from .filtering import (
    BestFilter,
    TrimFilter,
    expand_mask,
    feature_mask,
    index_best_codes,
)
from .postings import (
    Postings,
    build_postings,
    idf_weights,
    lookup,
    score_postings_batch,
)
from .quantize import QuantizedTable, quantize_table
from .rerank import brute_force_topk, normalize, rerank_topk

__all__ = ["VectorIndex", "SearchParams", "phase1_engine_scores",
           "FUSED_ENGINES", "encode_query_rows"]

# engines that fuse phase-1 scoring with candidate selection: they return
# the candidate page directly instead of a dense (Q, d) score matrix, so
# they dispatch around phase1_engine_scores (in both VectorIndex.search
# and the per-shard query phase in repro.dist.shard_index)
FUSED_ENGINES = ("fused", "fused_int8")

_SENTINEL = {  # never-matching code per dtype (outside any bucket range)
    jnp.int8.dtype: 127,
    jnp.int16.dtype: 32767,
    jnp.int32.dtype: 2**31 - 1,
}


def phase1_engine_scores(
    codes: jnp.ndarray,            # (d, C) document codes
    postings: Postings,
    qcodes: jnp.ndarray,           # (Q, C)
    col_weights: jnp.ndarray,      # (Q, C), 0 where the token is filtered
    engine: str,
    max_postings: Optional[int],
    max_abs_bucket: int,
) -> jnp.ndarray:
    """Phase-1 scores (Q, d) under the chosen engine.

    The single engine-dispatch point: both the single-device
    :meth:`VectorIndex.phase1_scores` and the per-shard query phase in
    :mod:`repro.dist.shard_index` go through here, so a new engine is
    automatically available (and parity-testable) in both.
    """
    if engine == "postings":
        L = postings.n_docs if max_postings is None else max_postings
        return score_postings_batch(
            postings,
            qcodes,
            col_weights > 0,
            max_postings=L,
            weighting="count",   # weights already folded into col_weights
            col_weights=col_weights,
        )
    if engine == "codes":
        return score_codes(codes, qcodes, col_weights)
    if engine == "codes_pallas":
        from repro.kernels.code_match import ops as cm_ops

        return cm_ops.code_match(codes, qcodes, col_weights)
    if engine == "onehot":
        return score_onehot(codes, qcodes, col_weights, max_abs_bucket)
    raise ValueError(f"unknown engine {engine!r}")


@partial(jax.jit, static_argnames=("encoder", "trim", "best"))
def encode_query_rows(q, *, encoder: Encoder, trim: Optional[TrimFilter],
                      best: Optional[BestFilter]):
    """(Q, n) float32 queries -> (unit queries, codes (Q, C), code-column
    mask (Q, C)), as one program: the search path then dispatches once
    per batch, not once per op (the pairwise norm alone is dozens)."""
    q = normalize(q)
    qcodes = encoder.encode(q)
    return q, qcodes, expand_mask(feature_mask(q, trim=trim, best=best),
                                  qcodes.shape[-1])


@dataclasses.dataclass(frozen=True)
class SearchParams:
    k: int = 10
    page: int = 320
    trim: Optional[TrimFilter] = None
    best: Optional[BestFilter] = None
    engine: str = "postings"  # postings|codes|onehot|codes_pallas|fused|fused_int8
    weighting: str = "idf"         # idf | count
    max_postings: Optional[int] = None  # None -> exact (= n_docs)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VectorIndex:
    """Immutable two-phase search index over unit-normalised vectors."""

    vectors: jnp.ndarray           # (d, n) f32, unit rows
    codes: jnp.ndarray             # (d, C) int
    postings: Postings
    encoder: Encoder
    index_best: Optional[int]      # index-side 'best' filter used at build

    # -- pytree plumbing (lets the whole index cross jit/shard boundaries) --
    def tree_flatten(self):
        return (self.vectors, self.codes, self.postings), (self.encoder, self.index_best)

    @classmethod
    def tree_unflatten(cls, aux, children):
        vectors, codes, postings = children
        encoder, index_best = aux
        return cls(vectors, codes, postings, encoder, index_best)

    # ------------------------------------------------------------------ build
    @classmethod
    def build(
        cls,
        vectors: jnp.ndarray,
        encoder: Encoder = RoundingEncoder(2),
        index_best: Optional[int] = None,
    ) -> "VectorIndex":
        vectors = normalize(jnp.asarray(vectors, jnp.float32))
        codes = encoder.encode(vectors)
        if index_best is not None:
            codes = index_best_codes(
                vectors, codes, index_best, _SENTINEL[codes.dtype])
        postings = build_postings(codes)
        return cls(vectors, codes, postings, encoder, index_best)

    @property
    def n_docs(self) -> int:
        return self.vectors.shape[0]

    @property
    def n_features(self) -> int:
        return self.vectors.shape[1]

    @property
    def quantized(self) -> QuantizedTable:
        """int8 per-row quantized copy of ``vectors`` for ``fused_int8``
        phase-1 selection.  Derived lazily (a pure function of the vector
        bits -- never persisted; recovered indexes re-derive identical
        tables) and cached per instance: every mutation path returns a
        new index, so the cache can never go stale (the ``max_df``
        pattern in dist/shard_index)."""
        cached = self.__dict__.get("_quant_cache")
        if cached is None:
            cached = quantize_table(self.vectors)
            self.__dict__["_quant_cache"] = cached
        return cached

    # ---------------------------------------------------------- query encode
    def encode_queries(
        self,
        queries: jnp.ndarray,
        trim: Optional[TrimFilter],
        best: Optional[BestFilter],
        weighting: str,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """-> (queries_normalised (Q,n), qcodes (Q,C), col_weights (Q,C))."""
        q, qcodes, mask = encode_query_rows(
            jnp.asarray(queries, jnp.float32), encoder=self.encoder,
            trim=trim, best=best)
        if weighting == "idf":
            lo, hi = jax.vmap(lambda qc: lookup(self.postings, qc))(qcodes)
            w = idf_weights(hi - lo, self.postings.n_docs)
        elif weighting == "count":
            w = jnp.ones(qcodes.shape, jnp.float32)
        else:
            raise ValueError(f"unknown weighting {weighting!r}")
        return q, qcodes, jnp.where(mask, w, 0.0)

    # ----------------------------------------------------------------- phase 1
    def phase1_scores(
        self,
        qcodes: jnp.ndarray,
        col_weights: jnp.ndarray,
        engine: str,
        max_postings: Optional[int],
    ) -> jnp.ndarray:
        return phase1_engine_scores(
            self.codes, self.postings, qcodes, col_weights, engine,
            max_postings, self.encoder.max_abs_bucket,
        )

    # ------------------------------------------------------------------ search
    def search(
        self,
        queries: jnp.ndarray,
        k: int = 10,
        page: int = 320,
        trim: Optional[TrimFilter] = None,
        best: Optional[BestFilter] = None,
        engine: str = "postings",
        weighting: str = "idf",
        max_postings: Optional[int] = None,
        profile=None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Two-phase search -> (ids (Q,k), cosine scores (Q,k)).

        The ``fused``/``fused_int8`` engines select the candidate page in
        one kernel pass (repro.kernels.fused_phase1) instead of
        materializing phase-1 scores; ``fused`` is bit-identical to
        ``codes`` selection, ``fused_int8`` trades candidate recall for
        4x fewer phase-1 bytes.  Phase 2 is the same exact-fp32 rerank
        for every engine.  ``fused_int8`` reads no tokens, so
        trim/best/weighting do not apply to it.

        ``profile`` is an optional :class:`repro.obs.profile.ProfileNode`
        that receives encode / phase1 / rescore children with host-side
        wall times (``jax.block_until_ready`` fences between phases; the
        fences change only *when* results are observed, never their
        values, so bit-parity pins hold with profiling on).
        """
        queries = jnp.atleast_2d(queries)
        page = min(page, self.n_docs)
        k = min(k, page)
        t_prof = time.monotonic() if profile is not None else 0.0
        if engine in FUSED_ENGINES:
            from repro.kernels.fused_phase1 import ops as fp_ops

            if engine == "fused":
                q, qcodes, w = self.encode_queries(
                    queries, trim, best, weighting)
                if profile is not None:
                    jax.block_until_ready((q, qcodes, w))
                    t_now = time.monotonic()
                    profile.child("encode", t_now - t_prof,
                                  n_queries=int(q.shape[0]))
                    t_prof = t_now
                _, cand = fp_ops.fused_phase1(self.codes, qcodes, w,
                                              page=page)
            else:
                q = normalize(jnp.asarray(queries, jnp.float32))
                if profile is not None:
                    jax.block_until_ready(q)
                    t_now = time.monotonic()
                    profile.child("encode", t_now - t_prof,
                                  n_queries=int(q.shape[0]))
                    t_prof = t_now
                qt = self.quantized
                _, cand = fp_ops.fused_phase1_quant(
                    qt.codes, qt.scale, qt.zero, q, page=page)
            if profile is not None:
                jax.block_until_ready(cand)
                t_now = time.monotonic()
                profile.child("phase1", t_now - t_prof, engine=engine,
                              kernel=engine, page=int(page), k=int(k),
                              candidates=int(cand.size))
                t_prof = t_now
            ids, scores = rerank_topk(self.vectors, cand, q, k)
            if profile is not None:
                jax.block_until_ready((ids, scores))
                profile.child("rescore", time.monotonic() - t_prof,
                              k=int(k))
            return ids, scores
        q, qcodes, w = self.encode_queries(queries, trim, best, weighting)
        if profile is not None:
            jax.block_until_ready((q, qcodes, w))
            t_now = time.monotonic()
            profile.child("encode", t_now - t_prof,
                          n_queries=int(q.shape[0]))
            t_prof = t_now
        scores1 = self.phase1_scores(qcodes, w, engine, max_postings)
        _, cand = jax.lax.top_k(scores1, page)                  # (Q, page)
        if profile is not None:
            jax.block_until_ready(cand)
            t_now = time.monotonic()
            profile.child("phase1", t_now - t_prof, engine=engine,
                          kernel="composed", page=int(page), k=int(k),
                          candidates=int(cand.size))
            t_prof = t_now
        ids, scores = rerank_topk(self.vectors, cand, q, k)
        if profile is not None:
            jax.block_until_ready((ids, scores))
            profile.child("rescore", time.monotonic() - t_prof, k=int(k))
        return ids, scores

    # ------------------------------------------------------------------- shard
    def shard(self, mesh) -> "ShardedVectorIndex":  # noqa: F821 (lazy import)
        """Partition this index into per-device doc-shards over ``mesh``'s
        ``data`` axis -> :class:`repro.dist.shard_index.ShardedVectorIndex`
        (same ``search`` contract; bit-identical for ``page >= n_docs``)."""
        from repro.dist.shard_index import ShardedVectorIndex

        return ShardedVectorIndex.from_index(self, mesh)

    def gold_topk(self, queries: jnp.ndarray, k: int = 10):
        """Paper's gold standard: brute-force cosine scan over all vectors.

        ``k`` clamps to ``n_docs``, matching :meth:`search`'s
        ``k = min(k, page) <= n_docs`` -- a corpus can't yield more hits
        than it has documents."""
        q = normalize(jnp.atleast_2d(jnp.asarray(queries, jnp.float32)))
        return brute_force_topk(self.vectors, q, min(k, self.n_docs))
