"""Public wrappers for the fused phase-1 kernel.

Backend selection follows the code_match convention: on TPU the compiled
Pallas kernel runs natively; on CPU small problems run the same kernel body
under ``interpret=True`` (what the tier-1 property sweeps exercise), and
large problems take a ``lax.scan`` STREAMING fallback -- the same
tile-score + stable-top-k fold, so it keeps the kernel's memory behaviour
(no (Q, d) score matrix) *and* its bit-exactness against the composed
reference.  All three implementations return identical bits for finite
scores: per-tile scores use the reference's elementary expression
unchunked, and the streamed fold is equivalent to one global stable top-k
(tie-breaks prefer lower doc ids, exactly like ``jax.lax.top_k`` over the
dense matrix).

Contract for -inf slots: when fewer than ``page`` candidates are live, the
trailing -inf slots carry an UNSPECIFIED (but always in-range) doc id --
the composed reference surfaces arbitrary dead ids there instead.  Every
consumer (dist/shard_index's merge, rerank) masks scores by liveness
before ids matter, so only the finite prefix is load-bearing; the parity
suite pins scores everywhere and ids wherever finite.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import (DEFAULT_BLOCK_D, DEFAULT_BLOCK_Q, FP32_BLOCK_D,
                     fused_phase1_pallas, fused_phase1_quant_pallas)

_INTERPRET_ELEMENT_LIMIT = 1 << 22  # interpret mode is python-speed; cap it
# doc-tile width of the scan fallback: 512 keeps the (Q, block, C) select
# intermediate inside cache -- measured 1.6x faster than 2048 at the
# BENCH_kernel_scale sizes, and the where/sum scorer is bit-invariant to
# the tile width (verified for odd widths too), so retuning never moves
# parity
_STREAM_BLOCK_D = 512


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_docs(arrs, live, d, block_d):
    """Pad doc-axis inputs to a BLOCK_D multiple; pad rows go live=False
    so they score -inf and can never displace a real candidate."""
    pad = (-d) % block_d
    if pad:
        arrs = [jnp.pad(a, ((0, pad), (0, 0))) for a in arrs]
        live = jnp.pad(live, (0, pad))
    return arrs, live


def _finish(scores, ids, Q, d):
    """Slice off query padding and clamp ids in-range (-inf slots may
    carry a padded doc id or the kernel's negative placeholder;
    everything downstream masks them by score, but an out-of-range id
    must never escape)."""
    return scores[:Q], jnp.clip(ids[:Q], 0, d - 1)


def _score_tile_codes(blk, qfree):
    from .ref import match_scores

    dc, = blk
    qc, w = qfree
    return match_scores(dc, qc, w)


def _score_tile_quant(blk, qfree):
    d8, sc, zp = blk
    q, qs = qfree
    raw = jax.lax.dot_general(
        q, d8.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return raw * sc[:, 0][None, :] + qs * zp[:, 0][None, :]


@partial(jax.jit, static_argnames=("score_tile", "page", "block_d"))
def _stream_fold(tiles, tile_lives, bases, qfree, score_tile, *, page,
                 block_d):
    """Shared scan fallback: score one doc tile at a time, fold into a
    running top-``page`` -- brute_force_topk's pattern, phase-1 scores."""
    Q = qfree[0].shape[0]

    def body(carry, inp):
        acc_s, acc_i = carry
        blk, lv, base = inp
        s = score_tile(blk, qfree)                      # (Q, block_d)
        s = jnp.where(lv[None, :], s, -jnp.inf)
        ids = base + jnp.arange(block_d, dtype=jnp.int32)
        cat_s = jnp.concatenate([acc_s, s], axis=1)
        cat_i = jnp.concatenate(
            [acc_i, jnp.broadcast_to(ids, (Q, block_d))], axis=1)
        ts, pos = jax.lax.top_k(cat_s, page)
        return (ts, jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((Q, page), -jnp.inf, jnp.float32),
            jnp.zeros((Q, page), jnp.int32))
    (acc_s, acc_i), _ = jax.lax.scan(body, init, (tiles, tile_lives, bases))
    return acc_s, acc_i


def _stream(doc_arrs, live, qfree, score_tile, page, d):
    """Reshape doc-axis inputs into scan tiles and fold."""
    doc_arrs, live = _pad_docs(doc_arrs, live, d, _STREAM_BLOCK_D)
    nb = live.shape[0] // _STREAM_BLOCK_D
    tiles = tuple(a.reshape(nb, _STREAM_BLOCK_D, a.shape[-1])
                  for a in doc_arrs)
    tile_lives = live.reshape(nb, _STREAM_BLOCK_D)
    bases = (jnp.arange(nb) * _STREAM_BLOCK_D).astype(jnp.int32)
    return _stream_fold(tiles, tile_lives, bases, qfree, score_tile,
                        page=page, block_d=_STREAM_BLOCK_D)


def fused_phase1(
    doc_codes: jnp.ndarray,    # (d, C) int
    qcodes: jnp.ndarray,       # (Q, C) int
    col_weights: jnp.ndarray,  # (Q, C) f32
    page: int,
    live: Optional[jnp.ndarray] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_d: int = FP32_BLOCK_D,
    force_pallas: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused fp32 phase-1: code-match scores + top-``page`` in one pass
    -> (scores (Q, page) f32, ids (Q, page) int32), bit-identical to
    ``ref.fused_phase1_ref`` (scores everywhere; ids where finite)."""
    d, C = doc_codes.shape
    Q = qcodes.shape[0]
    page = int(min(page, d))
    lv = jnp.ones((d,), bool) if live is None else live

    on_tpu = _on_tpu()
    if not on_tpu and not force_pallas and Q * d * C > _INTERPRET_ELEMENT_LIMIT:
        s, i = _stream((doc_codes,), lv, (qcodes, col_weights),
                       _score_tile_codes, page, d)
        return _finish(s, i, Q, d)

    block_q = min(block_q, max(Q, 1))
    pad_q = (-Q) % block_q
    qc = jnp.pad(qcodes, ((0, pad_q), (0, 0)))
    w = jnp.pad(col_weights, ((0, pad_q), (0, 0)))
    s, i = fused_phase1_pallas(doc_codes, qc, w, lv, page=page,
                               block_q=block_q, block_d=block_d,
                               interpret=not on_tpu)
    return _finish(s, i, Q, d)


def fused_phase1_quant(
    qcodes8: jnp.ndarray,      # (d, n) int8 quantized rows
    scale: jnp.ndarray,        # (d,) f32
    zero: jnp.ndarray,         # (d,) f32
    queries: jnp.ndarray,      # (Q, n) f32
    page: int,
    live: Optional[jnp.ndarray] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_d: int = DEFAULT_BLOCK_D,
    force_pallas: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused int8 phase-1: quantized-dot scores + top-``page`` in one
    pass.  Candidate selection only -- callers rescore the returned page
    against the exact fp32 vectors."""
    d, n = qcodes8.shape
    Q = queries.shape[0]
    page = int(min(page, d))
    lv = jnp.ones((d,), bool) if live is None else live
    qsum = jnp.sum(queries, axis=-1, keepdims=True)     # (Q, 1)

    on_tpu = _on_tpu()
    if not on_tpu and not force_pallas and Q * d * n > _INTERPRET_ELEMENT_LIMIT:
        s, i = _stream((qcodes8, scale[:, None], zero[:, None]), lv,
                       (queries, qsum), _score_tile_quant, page, d)
        return _finish(s, i, Q, d)

    block_q = min(block_q, max(Q, 1))
    block_d = min(block_d, max(d, 1))
    pad_q = (-Q) % block_q
    q = jnp.pad(queries, ((0, pad_q), (0, 0)))
    qs = jnp.pad(qsum, ((0, pad_q), (0, 0)))
    s, i = fused_phase1_quant_pallas(
        qcodes8, scale, zero, q, qs, lv, page=page, block_q=block_q,
        block_d=block_d, interpret=not on_tpu)
    return _finish(s, i, Q, d)
