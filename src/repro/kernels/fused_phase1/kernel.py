"""Fused phase-1 Pallas kernels: tiled scoring + running top-k in one pass.

The composed hot path scores every document, writes the full (Q, d) score
matrix to HBM, reads it back for ``top_k(page)``, and throws it away --
2 x Q x d x 4 bytes of HBM traffic that dwarfs the code table itself once
d is large.  These kernels keep a running top-``page`` accumulator in a
resident output block instead, so the score matrix never exists:

* int8 mode: grid = (Q / BLOCK_Q, d / BLOCK_D), with the DOC axis as the
  minor (fastest-moving) grid dimension -- for a fixed query tile the
  kernel walks every doc tile in order, and the output BlockSpec ignores
  the doc index, so the same (BLOCK_Q, page) scores/ids block stays
  resident in VMEM across the whole doc sweep; each step scores one
  (BLOCK_Q, BLOCK_D) tile on the MXU (int8 dot + per-row affine
  correction);
* fp32 mode: grid = (d / BLOCK_D, Q / BLOCK_Q), with the QUERY axis
  minor, and the whole (Q, page) accumulator resident across the grid;
  each step folds into its own BLOCK_Q rows.  A doc tile (up to 8,192
  docs, fewer where VMEM cannot hold them) is widened to int32 and
  transposed into VMEM once per batch, on its first query tile, so the
  code columns C run down the sublanes and the docs along the lanes;
* each scored tile has dead docs masked to -inf and is folded into the
  accumulator (:func:`_fold`) -- in fp32 mode one 512-doc group at a
  time, so a fold pass costs what it does in int8 mode;
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

The fp32 scorer works on (8, 128) vregs: 8 code columns by 128 docs.
Each step first lays the query tile out as lane tables -- query r's
columns down the sublanes, each broadcast across the lanes -- so that a
leaf, 8 columns of 512 docs, is one compare and one select per vreg
against its query's 8 codes and weights.  Per query and 512-doc group
it sums the C columns with ref.match_scores' pairwise tree, walked depth
first (:func:`_pairwise`), so the partial sums stay in registers:

* the tree's levels with half-width h >= 8 pair vreg k with vreg
  k + h/8 elementwise: the same rows the reference adds;
* its last three levels are within the vreg, sublane i with i + 4, + 2
  and + 1, done as rolls (every sublane ends up with the total);
* the columns are zero-padded to the tree's power of two with weight
  0.0: a padding row inside the last vreg is added as the reference adds
  it, and a vreg that is all padding is skipped, since x + 0.0 == x for
  these non-negative sums.

The same pairs are added in the same order, so the per-cell bits equal
the full-matrix oracle's however the docs and queries are tiled.

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
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 8
DEFAULT_BLOCK_D = 512
# fp32: the most docs per grid step (widened and transposed once per
# batch; fewer where VMEM cannot hold them), and docs per scored group
# (one fold each)
FP32_BLOCK_D = 8192
_GROUP_D = 512
_LANES = 128
# VMEM the fp32 kernel may ask for, of the 128 MiB a v5e core has
_VMEM_CAP = 100 << 20

_I32_MIN = jnp.iinfo(jnp.int32).min
_I32_MAX = jnp.iinfo(jnp.int32).max


def _pairwise(leaf, m0, step, n):
    """ref.match_scores' pairwise tree over the leaves ``m0 + step * t``
    (t < n; n a power of two), depth first: each subtree is summed before
    its sibling, so about one partial per level is live.  ``leaf``
    returns None for a leaf that is all padding; a missing partner is the
    padding's 0.0, and ``x + 0.0 == x`` for the non-negative sums here,
    so the pair is just ``x``."""
    if n == 1:
        return leaf(m0)
    a = _pairwise(leaf, m0, 2 * step, n // 2)
    b = _pairwise(leaf, m0 + step, 2 * step, n // 2)
    if b is None:
        return a
    return a + b


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


def _fold_step(s, base, first, live, os_ref, oi_ref, n_docs):
    """Mask dead docs and the docs past ``n_docs`` of a ragged last tile
    (``s``'s column 0 is doc ``base``), then fold the scored tile into
    the accumulator held in ``os_ref``/``oi_ref`` (initialised when
    ``first`` with -inf and distinct negative ids)."""

    @pl.when(first)
    def _init():
        os_ref[...] = jnp.full(os_ref.shape, -jnp.inf, jnp.float32)
        oi_ref[...] = -1 - jax.lax.broadcasted_iota(
            jnp.int32, oi_ref.shape, 1)

    ids = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where((live != 0) & (ids < n_docs), s, -jnp.inf)
    os_ref[...], oi_ref[...] = _fold(os_ref[...], oi_ref[...], s, ids)


def _fused_kernel(q_ref, w_ref, d_ref, lv_ref, os_ref, oi_ref, dt_ref,
                  tc_ref, tw_ref, *, block_q: int, block_d: int,
                  group_d: int, n_docs: int):
    """fp32 code-match scores + running top-k fold (see the module doc).
    Step (doc tile j, query tile i); ``dt_ref`` holds doc tile j widened
    and transposed, ``tc_ref``/``tw_ref`` the query tile's lane tables
    (``tc_ref[r, c, :]`` is query r's code in column c)."""
    j, i = pl.program_id(0), pl.program_id(1)
    n_cols = d_ref.shape[1]
    p2 = 1 << max(n_cols - 1, 0).bit_length()
    n_vregs = -(-n_cols // 8)
    # groups of this tile that hold docs (the last tile may be ragged)
    n_groups = jnp.minimum(block_d // group_d,
                           pl.cdiv(n_docs - j * block_d, group_d))
    docs = lambda g: pl.ds(pl.multiple_of(g * group_d, group_d), group_d)

    @pl.when(i == 0)
    def _transpose_docs():
        def body(g, c):
            dt_ref[:n_cols, docs(g)] = d_ref[docs(g), :].astype(jnp.int32).T
            return c
        jax.lax.fori_loop(0, n_groups, body, 0)

    rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
    qs, ws = q_ref[rows, :], w_ref[rows, :]
    for r in range(block_q):
        tc_ref[r, :n_cols, :] = jnp.broadcast_to(
            qs[r:r + 1], (_LANES, n_cols)).T
        tw_ref[r, :n_cols, :] = jnp.broadcast_to(
            ws[r:r + 1], (_LANES, n_cols)).T
    if tw_ref.shape[1] > n_cols:    # the last vreg's padding weighs 0.0
        tw_ref[:, n_cols:, :] = jnp.zeros(
            (block_q, tw_ref.shape[1] - n_cols, _LANES), jnp.float32)

    reps = -(-group_d // _LANES)
    lanes = lambda x: jnp.concatenate([x] * reps, axis=1)[:, :group_d]

    def score(g, r):
        """Query r's scores of group g, in every row of an (8, group_d)
        tile, or in row 0 of it when the tree is under 8 columns."""
        def leaf(v):
            if v >= n_vregs:
                return None
            at = pl.ds(v * 8, 8)
            return jnp.where(dt_ref[at, docs(g)] == lanes(tc_ref[r, at, :]),
                             lanes(tw_ref[r, at, :]), 0.0)

        x = _pairwise(leaf, 0, 1, max(p2 // 8, 1))
        if p2 >= 8:
            for h in (4, 2, 1):
                x = x + pltpu.roll(x, h, 0)
        else:
            for h in (2, 1):
                if h < p2:
                    x = x[:h] + x[h:2 * h]
        return x

    row = jax.lax.broadcasted_iota(jnp.int32, (block_q, group_d), 0)

    def group(g, c):
        def one(r, s):
            x = score(g, r)
            if p2 < 8 or block_q > 8:
                x = jnp.broadcast_to(x[:1], (block_q, group_d))
            return jnp.where(row == r, x[:block_q], s)

        s = jax.lax.fori_loop(0, block_q, one,
                              jnp.zeros((block_q, group_d), jnp.float32))
        _fold_step(s, j * block_d + g * group_d, (j == 0) & (g == 0),
                   lv_ref[:, docs(g)], os_ref.at[rows], oi_ref.at[rows],
                   n_docs)
        return c

    jax.lax.fori_loop(0, n_groups, group, 0)


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
    j = pl.program_id(1)
    _fold_step(s, j * block_d, j == 0, lv_ref[...], os_ref, oi_ref, n_docs)


def _ordered(scores, ids):
    """Sort each row by (score descending, id ascending)."""
    neg, ids = jax.lax.sort((-scores, ids), dimension=1, num_keys=2)
    return -neg, ids


def _call(kernel, name, doc_inputs, lane_inputs, q_inputs, Q, d, page,
          block_q, block_d, interpret):
    """The int8 kernel's pallas_call plumbing (the fp32 kernel has its
    own grid, in :func:`fused_phase1_pallas`): query-tile inputs
    replicate over the doc grid axis, doc-tile inputs over the query
    axis, lane-dense (1, d) per-doc rows tile along lanes, and both
    outputs revisit the same (BLOCK_Q, page) block for every doc tile.
    The last doc tile may run past ``d``; the kernel masks those rows, so
    the tables are never padded (a padded copy of a table costs its size
    again).  ``name`` names the kernel's op in HLO and in a profiler
    trace, whatever calls it."""
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


def _vmem_limit(Q, C, page, block_q, block_d):
    """Bytes of VMEM the fp32 kernel asks for: its buffers, in padded
    (8, 128) tiles, and room for the compiler's own."""
    up = lambda n, m: -(-n // m) * m
    c8, cl = up(C, 8), up(C, _LANES)
    need = (2 * up(block_d, 32) * cl             # doc tile, int8, 2 buffers
            + 2 * 2 * 4 * up(Q, 8) * cl          # query codes and weights
            + 2 * 2 * 4 * up(Q, 8) * up(page, _LANES)  # scores and ids
            + 2 * 4 * 8 * up(block_d, _LANES)    # live row
            + 4 * c8 * up(block_d, _LANES)       # the transposed tile
            + 2 * 4 * block_q * c8 * _LANES)     # the lane tables
    return need + (8 << 20)


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
    block_d: int = FP32_BLOCK_D,
    interpret: bool = False,
):
    """fp32 pallas call over a block_q-padded batch; use :mod:`.ops`.
    Grid (doc tile, query tile) with the query tile minor, so each doc
    tile is widened and transposed once per batch; the queries and the
    whole (Q, page) accumulator stay resident in VMEM across the grid,
    and each step folds into its own block_q rows."""
    d, C = doc_codes.shape
    Q = qcodes.shape[0]
    assert Q % block_q == 0, (Q, block_q)
    block_d = min(block_d, -(-d // _LANES) * _LANES)
    while (block_d > _GROUP_D and block_d % (2 * _LANES) == 0
           and _vmem_limit(Q, C, page, block_q, block_d) > _VMEM_CAP):
        block_d //= 2
    group_d = _GROUP_D if block_d % _GROUP_D == 0 else block_d
    c8 = -(-C // 8) * 8
    whole = lambda j, i: (0, 0)
    s, i = pl.pallas_call(
        functools.partial(_fused_kernel, block_q=block_q, block_d=block_d,
                          group_d=group_d, n_docs=d),
        grid=(pl.cdiv(d, block_d), Q // block_q),
        in_specs=[pl.BlockSpec((Q, C), whole), pl.BlockSpec((Q, C), whole),
                  pl.BlockSpec((block_d, C), lambda j, i: (j, 0)),
                  pl.BlockSpec((1, block_d), lambda j, i: (0, j))],
        out_specs=[pl.BlockSpec((Q, page), whole)] * 2,
        out_shape=[jax.ShapeDtypeStruct((Q, page), jnp.float32),
                   jax.ShapeDtypeStruct((Q, page), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((c8, block_d), jnp.int32),
                        pltpu.VMEM((block_q, c8, _LANES), jnp.int32),
                        pltpu.VMEM((block_q, c8, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(Q, C, page, block_q, block_d)),
        interpret=interpret,
        name="fused_phase1_pallas",
    )(qcodes.astype(jnp.int32), col_weights, doc_codes,
      _lane_row(live, jnp.int32))
    return _ordered(s, i)


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
