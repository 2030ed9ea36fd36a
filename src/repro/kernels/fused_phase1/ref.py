"""Pure-jnp oracles for the fused phase-1 kernel.

Each oracle is the COMPOSED path the fused kernel replaces: materialize the
full (Q, d) phase-1 score matrix, mask dead rows, then one global stable
``top_k(page)``.  The fused kernel must match these bit-exactly in fp32
(scores always; ids wherever the score is finite -- see ops.py for the
-inf-slot contract).

:func:`match_scores` is the ONE scoring expression the whole fp32 family
shares (this oracle, the Pallas kernel body, the streaming fallback, and
the sharded generation scorer): select then a MANUAL pairwise-tree sum
over the code columns, zero-padded to a power of two
(:func:`repro.core.rerank.pairwise_sum`).  Every tree step is
an elementwise add of two halves, so the reduction order is a pure
function of C -- the bits cannot depend on how the doc or query axis is
tiled.  A ``jnp.sum`` over C does NOT have that property: XLA picks the
reduction order per tensor shape, and blocked vs full scoring then
disagrees in the last ulp for some (tile, C) combinations.  (Zero-padding
is exact: scores are sums of non-negative weights, and x + 0.0 == x for
every such float.)  The tree also benches slightly faster than the
where/sum form at the stream tile size.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantize import quantized_scores
from repro.core.rerank import pairwise_sum


def match_scores(doc_codes: jnp.ndarray,    # (d, C) int
                 qcodes: jnp.ndarray,       # (Q, C) int
                 col_weights: jnp.ndarray,  # (Q, C) f32
                 ) -> jnp.ndarray:
    """Code-match scores (Q, d): select the matching weights, then sum
    the C axis with a fixed pairwise tree.  Bit-invariant to doc/query
    tiling (see module doc)."""
    x = jnp.where(qcodes[:, None, :] == doc_codes[None, :, :],
                  col_weights[:, None, :], 0.0)          # (Q, d, C)
    return pairwise_sum(x)


def _mask_topk(scores: jnp.ndarray, live: Optional[jnp.ndarray],
               page: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if live is not None:
        scores = jnp.where(live[None, :], scores, -jnp.inf)
    top_s, top_i = jax.lax.top_k(scores, min(page, scores.shape[1]))
    return top_s, top_i.astype(jnp.int32)


def fused_phase1_ref(
    doc_codes: jnp.ndarray,    # (d, C) int
    qcodes: jnp.ndarray,       # (Q, C) int
    col_weights: jnp.ndarray,  # (Q, C) f32
    page: int,
    live: Optional[jnp.ndarray] = None,   # (d,) bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Composed fp32 reference: code_match scores -> mask -> top_k(page)."""
    scores = match_scores(doc_codes, qcodes, col_weights)
    return _mask_topk(scores, live, page)


def fused_phase1_quant_ref(
    qcodes8: jnp.ndarray,     # (d, n) int8 quantized rows
    scale: jnp.ndarray,       # (d,) f32
    zero: jnp.ndarray,        # (d,) f32
    queries: jnp.ndarray,     # (Q, n) f32
    page: int,
    live: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Composed int8 reference: quantized_scores -> mask -> top_k(page)."""
    qsum = jnp.sum(queries, axis=-1, keepdims=True)
    scores = quantized_scores(qcodes8, scale, zero, queries, qsum=qsum)
    return _mask_topk(scores, live, page)
