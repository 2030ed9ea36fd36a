"""Fused phase-1 Pallas kernel: tiled scoring + running top-k in one pass.

The composed hot path scores every document, writes the full (Q, d) score
matrix to HBM, reads it back for ``top_k(page)``, and throws it away --
2 x Q x d x 4 bytes of HBM traffic that dwarfs the code table itself once
d is large.  This kernel keeps a running top-``page`` accumulator in the
revisited output block instead, so the score matrix never exists:

* grid = (Q / BLOCK_Q, d / BLOCK_D), with the DOC axis as the minor
  (fastest-moving) grid dimension -- for a fixed query tile the kernel
  walks every doc tile in order, and the output BlockSpec ignores the doc
  index, so the same (BLOCK_Q, page) scores/ids block stays resident in
  VMEM across the whole doc sweep (the standard revisited-accumulator
  pattern);
* each step scores one (BLOCK_Q, BLOCK_D) tile -- weighted code equality
  in fp32 mode, the int8 dot + per-row affine correction in quantized
  mode -- masks dead rows to -inf, and folds the tile into the
  accumulator (:func:`_fold`);
* the fold is built from max/min/where and a while loop, the operations
  Mosaic lowers (it has no sort or top_k).  The accumulator is an
  unordered set of ``page`` (score, id) pairs.  While the tile's best
  entry (highest score, lowest id among ties) scores strictly above the
  accumulator's worst (lowest score, highest id among ties), the best
  replaces the worst.  Doc tiles arrive in id order, so a tile entry that
  only ties the worst has the higher id and rightly stays out: the
  accumulator always holds the top ``page`` of the docs seen so far under
  (score descending, id ascending) -- one global stable top-k.  A tile
  whose best does not beat the worst does no fold work.  (+0.0 and -0.0
  tie here, where ``top_k`` ranks +0.0 first; the fp32 scores are sums
  of non-negative weights and never -0.0);
* after the grid, XLA sorts each row by (score descending, id ascending),
  the order ``jax.lax.top_k`` gives over the dense matrix.  Scores are
  untouched by the fold, so they stay bit-identical to the composed
  reference.

The fp32 scorer transposes the tile so the code columns C run down the
sublanes, scores one query at a time as a 2-D (C, BLOCK_D) select, and
sums C with ref.match_scores' pairwise tree (:func:`_tree_rows`): the
same pairs added in the same order, so the per-cell bits are identical
to the full-matrix oracle no matter how the doc axis is tiled.

Per-doc inputs (live mask, int8 scale and zero point) arrive lane-dense
as (1, d) rows; a (d, 1) column would pad every doc out to 128 lanes.
Slots that never see a finite score report -inf with an unspecified id
(ops.py documents this contract and clamps ids in range).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 8
DEFAULT_BLOCK_D = 512

_I32_MIN = jnp.iinfo(jnp.int32).min
_I32_MAX = jnp.iinfo(jnp.int32).max


def _tree_rows(x):
    """ref.match_scores' pairwise tree over axis 0 of a (C, BD) tile ->
    (1, BD).  Rows are zero-padded to a power of two and halved; only the
    first level can be ragged, and there the rows with no partner add the
    padding's 0.0 explicitly, as the reference does."""
    p2 = 1 << max(x.shape[0] - 1, 0).bit_length()
    while p2 > 1:
        h = p2 // 2
        n = x.shape[0]
        if n < p2:
            x = jnp.concatenate([x[:n - h] + x[h:], x[n - h:h] + 0.0],
                                axis=0)
        else:
            x = x[:h] + x[h:]
        p2 = h
    return x


def _fold(acc_s, acc_i, tile_s, tile_i):
    """Fold a (BQ, BD) tile into the (BQ, page) accumulator, one
    replacement per row per iteration (see the module doc)."""

    def bounds(acc_s, tile_s):
        return (jnp.min(acc_s, axis=1, keepdims=True),
                jnp.max(tile_s, axis=1, keepdims=True))

    def cond(c):
        lo, hi = c[3], c[4]
        return jnp.max((hi > lo).astype(jnp.int32)) > 0

    def body(c):
        acc_s, acc_i, tile_s, lo, hi = c
        go = hi > lo
        out_id = jnp.max(jnp.where(acc_s == lo, acc_i, _I32_MIN),
                         axis=1, keepdims=True)
        in_id = jnp.min(jnp.where(tile_s == hi, tile_i, _I32_MAX),
                        axis=1, keepdims=True)
        put = go & (acc_i == out_id)
        acc_s = jnp.where(put, hi, acc_s)
        acc_i = jnp.where(put, in_id, acc_i)
        tile_s = jnp.where(go & (tile_i == in_id), -jnp.inf, tile_s)
        return (acc_s, acc_i, tile_s) + bounds(acc_s, tile_s)

    out = jax.lax.while_loop(
        cond, body, (acc_s, acc_i, tile_s) + bounds(acc_s, tile_s))
    return out[0], out[1]


def _fold_step(s, lv_ref, os_ref, oi_ref, block_d, n_docs):
    """Mask dead docs and the rows past ``n_docs`` of a ragged last tile,
    then fold the scored tile into the accumulator held in the output
    block (initialised on the first doc tile with -inf and distinct
    negative ids)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        os_ref[...] = jnp.full(os_ref.shape, -jnp.inf, jnp.float32)
        oi_ref[...] = -1 - jax.lax.broadcasted_iota(
            jnp.int32, oi_ref.shape, 1)

    ids = j * block_d + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where((lv_ref[...] != 0) & (ids < n_docs), s, -jnp.inf)
    os_ref[...], oi_ref[...] = _fold(os_ref[...], oi_ref[...], s, ids)


def _fused_kernel(q_ref, w_ref, d_ref, lv_ref, os_ref, oi_ref, *,
                  block_d: int, n_docs: int):
    """fp32 code-match tile + running top-k fold."""
    dt = d_ref[...].astype(jnp.int32).T        # (C, BD)
    qt = q_ref[...].T                          # (C, BQ) int32
    wt = w_ref[...].T                          # (C, BQ) f32
    rows = [_tree_rows(jnp.where(dt == qt[:, r:r + 1], wt[:, r:r + 1], 0.0))
            for r in range(qt.shape[1])]
    s = jnp.concatenate(rows, axis=0)          # (BQ, BD)
    _fold_step(s, lv_ref, os_ref, oi_ref, block_d, n_docs)


def _fused_quant_kernel(q_ref, qsum_ref, d8_ref, sc_ref, zp_ref, lv_ref,
                        os_ref, oi_ref, *, block_d: int, n_docs: int):
    """int8 quantized-dot tile + running top-k fold.  Scores the
    dequantized rows without materializing them:
    ``scale * (codes . query) + zero * sum(query)``."""
    q = q_ref[...]                             # (BQ, n) f32
    d8 = d8_ref[...].astype(jnp.float32)       # (BD, n)
    raw = jax.lax.dot_general(
        q, d8, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)    # (BQ, BD)
    s = raw * sc_ref[...] + qsum_ref[...] * zp_ref[...]
    _fold_step(s, lv_ref, os_ref, oi_ref, block_d, n_docs)


def _ordered(scores, ids):
    """Sort each row by (score descending, id ascending)."""
    neg, ids = jax.lax.sort((-scores, ids), dimension=1, num_keys=2)
    return -neg, ids


def _call(kernel, name, doc_inputs, lane_inputs, q_inputs, Q, d, page,
          block_q, block_d, interpret):
    """Shared pallas_call plumbing: query-tile inputs replicate over the
    doc grid axis, doc-tile inputs over the query axis, lane-dense (1, d)
    per-doc rows tile along lanes, and both outputs revisit the same
    (BLOCK_Q, page) block for every doc tile.  The last doc tile may run
    past ``d``; the kernel masks those rows, so the tables are never
    padded (a padded copy of a table costs its size again).  ``name``
    names the kernel's op in HLO and in a profiler trace, whatever calls
    it."""
    grid = (Q // block_q, pl.cdiv(d, block_d))
    q_specs = [pl.BlockSpec((block_q, x.shape[-1]), lambda i, j: (i, 0))
               for x in q_inputs]
    d_specs = [pl.BlockSpec((block_d, x.shape[-1]), lambda i, j: (j, 0))
               for x in doc_inputs]
    l_specs = [pl.BlockSpec((1, block_d), lambda i, j: (0, j))
               for _ in lane_inputs]
    out_spec = pl.BlockSpec((block_q, page), lambda i, j: (i, 0))
    s, i = pl.pallas_call(
        functools.partial(kernel, block_d=block_d, n_docs=d),
        grid=grid,
        in_specs=q_specs + d_specs + l_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((Q, page), jnp.float32),
                   jax.ShapeDtypeStruct((Q, page), jnp.int32)],
        interpret=interpret,
        name=name,
    )(*q_inputs, *doc_inputs, *lane_inputs)
    return _ordered(s, i)


def _lane_row(x, dtype):
    return x.astype(dtype).reshape(1, -1)


@functools.partial(
    jax.jit, static_argnames=("page", "block_q", "block_d", "interpret"))
def fused_phase1_pallas(
    doc_codes: jnp.ndarray,    # (d, C) int
    qcodes: jnp.ndarray,       # (Q, C) int
    col_weights: jnp.ndarray,  # (Q, C) f32
    live: jnp.ndarray,         # (d,) bool
    page: int,
    block_q: int = DEFAULT_BLOCK_Q,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
):
    """fp32 pallas call over a block_q-padded batch; use :mod:`.ops`."""
    d, _ = doc_codes.shape
    Q = qcodes.shape[0]
    assert Q % block_q == 0, (Q, block_q)
    return _call(_fused_kernel, "fused_phase1_pallas", [doc_codes],
                 [_lane_row(live, jnp.int32)],
                 [qcodes.astype(jnp.int32), col_weights], Q, d, page,
                 block_q, block_d, interpret)


@functools.partial(
    jax.jit, static_argnames=("page", "block_q", "block_d", "interpret"))
def fused_phase1_quant_pallas(
    qcodes8: jnp.ndarray,      # (d, n) int8
    scale: jnp.ndarray,        # (d,) f32
    zero: jnp.ndarray,         # (d,) f32
    queries: jnp.ndarray,      # (Q, n) f32
    qsum: jnp.ndarray,         # (Q, 1) f32 precomputed row sums
    live: jnp.ndarray,         # (d,) bool
    page: int,
    block_q: int = DEFAULT_BLOCK_Q,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
):
    """int8 pallas call over a block_q-padded batch; use :mod:`.ops`."""
    d, _ = qcodes8.shape
    Q = queries.shape[0]
    assert Q % block_q == 0, (Q, block_q)
    lanes = [_lane_row(scale, jnp.float32), _lane_row(zero, jnp.float32),
             _lane_row(live, jnp.int32)]
    return _call(_fused_quant_kernel, "fused_phase1_quant_pallas",
                 [qcodes8], lanes, [queries, qsum], Q, d, page, block_q,
                 block_d, interpret)
