"""The plain reference of ``refs/token_match.py``, computed in row blocks.

Same semantics, band and envelope as ``token_match`` (it reuses that
module's tokeniser, phase-1 sum, cosine and top-k pieces): only where the
work is held differs.  ``token_match`` keeps three corpus-sized tables on
the chips -- the rows, their unit rows and their tokens -- and makes the
tokens through a full-size int32 intermediate.  At 1,105,280 x 768 that
is 8.5 GB held and a 15.3 GB peak per reference, so the float32 reference
and the bfloat16 control (``bench/controls.py`` makes the second while the
first is alive) cannot share one 16 GB chip.  This one keeps the rows and
their int8 tokens only (5.1 GB), and makes the unit rows, tokens,
phase-1 scores and cosines block by block of ``BLOCK_ROWS`` rows on each
shard.

A unit row, its tokens and its cosine are functions of that row alone, so
the blocks give what the whole table gives; the phase-1 sum and the top-k
steps are ``token_match``'s own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from bench.refs import token_match as tm

# rows per step of the block loops: the loops slice the rows in place, so
# a step holds (Q, BLOCK_ROWS, C) at most; a shard's last step takes the
# remainder
BLOCK_ROWS = 8192


class Reference(tm.Reference):
    """The corpus of one seed on the chips, its int8 tokens and their
    document frequencies; no unit-row table."""

    def __init__(self, cfg: dict, seed: int, precision: str = "float32"):
        from bench import corpus

        self.cfg = cfg
        self.seed = seed
        self.precision = precision
        devices = jax.devices()[:cfg["n_shards"]]
        self.mesh = Mesh(np.array(devices), ("docs",))
        self.shards = cfg["n_shards"]
        self.n = cfg["n_docs"]
        mix = cfg["corpus"]
        self.raw = corpus.corpus(seed, self.n, cfg["n_features"],
                                 mix["n_topics"], mix["noise"], self.mesh)
        self.unit = None
        enc = cfg["encoder"]
        self.codes, self.hist = _prepare(
            self.raw, mesh=self.mesh, precision=precision,
            rp=enc["rounding_precision"], width=enc["interval_width"],
            nb=tm._bucket_range(cfg))

    def envelope(self, queries):
        """-> (ids_lo, ids_hi), each (Q, k): ``token_match``'s envelope."""
        lo_all, hi_all = [], []
        for n, (q, qc, w) in self._chunks(queries):
            lo, hi = _envelope(self.raw, self.codes, q, qc, w,
                               mesh=self.mesh, precision=self.precision,
                               page=self.cfg["page"], k=self.cfg["k"])
            lo_all.append(np.asarray(lo)[:n])
            hi_all.append(np.asarray(hi)[:n])
        return np.concatenate(lo_all), np.concatenate(hi_all)

    def answer(self, queries):
        """-> (ids, scores), each (Q, k): ``token_match``'s answer, in this
        reference's precision."""
        ids_all, sc_all = [], []
        for n, (q, qc, w) in self._chunks(queries):
            ids, sc = _answer(self.raw, self.codes, q, qc, w,
                              mesh=self.mesh, precision=self.precision,
                              page=self.cfg["page"], k=self.cfg["k"])
            ids_all.append(np.asarray(ids)[:n])
            sc_all.append(np.asarray(sc, np.float32)[:n])
        return np.concatenate(ids_all), np.concatenate(sc_all)


def _for_rows(f, xs, outs, axes):
    """Apply ``f`` to each ``BLOCK_ROWS``-row slice of the ``xs`` (rows on
    axis 0) and write its results into ``outs``, each at the same rows
    along its axis in ``axes``.  Slices in place: no copy of an ``x``."""
    n = xs[0].shape[0]
    step = min(BLOCK_ROWS, n)
    full, rest = divmod(n, step)

    def put(outs, res, lo):
        return tuple(jax.lax.dynamic_update_slice_in_dim(o, r, lo, a)
                     for o, r, a in zip(outs, res, axes))

    def body(i, outs):
        lo = i * step
        return put(outs, f(*(jax.lax.dynamic_slice_in_dim(x, lo, step)
                             for x in xs)), lo)

    outs = jax.lax.fori_loop(0, full, body, tuple(outs))
    if rest:
        outs = put(outs, f(*(x[full * step:] for x in xs)), full * step)
    return outs


@functools.partial(jax.jit, static_argnames=("mesh", "precision", "rp",
                                             "width", "nb"))
def _prepare(x, *, mesh, precision, rp, width, nb):
    """-> (int8 tokens (n, C) sharded like ``x``, global token histogram
    (C, 2nb+1) int32): ``token_match._prepare`` without the unit rows."""
    dt = tm._dt(precision)
    ax = mesh.axis_names[0]

    def local(xl):
        tokens = lambda v: (tm._tokens(tm._unit(v.astype(dt)), rp, width)
                            .astype(jnp.int8),)
        codes, = _for_rows(tokens, (xl,),
                           (jnp.zeros((xl.shape[0], 2 * xl.shape[1]),
                                      jnp.int8),), (0,))
        hist = jnp.stack([jnp.sum(codes == b, axis=0, dtype=jnp.int32)
                          for b in range(-nb, nb + 1)], axis=-1)
        return codes, jax.lax.psum(hist, ax)

    return jax.shard_map(local, mesh=mesh, in_specs=P(ax),
                         out_specs=(P(ax), P()), check_vma=False)(x)


def _scores(raw, codes, q, qc, w, *, mesh, precision):
    """Phase-1 scores and cosines, each (Q, S, dp) float32, made block by
    block of rows on each shard."""
    dt = tm._dt(precision)
    ax = mesh.axis_names[0]
    nq = q.shape[0]

    def local(xl, cl, q, qc, w):
        def one(v, c):
            s1 = tm._phase1(c, qc, w).astype(jnp.float32)
            cos = tm._cos(tm._unit(v.astype(dt)), q).astype(jnp.float32)
            return s1, cos                              # (Q, rows) each

        empty = jnp.zeros((nq, xl.shape[0]), jnp.float32)
        s1, cos = _for_rows(one, (xl, cl), (empty, empty), (1, 1))
        return s1[:, None], cos[:, None]

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(ax), P(ax), P(), P(), P()),
                         out_specs=(P(None, ax), P(None, ax)),
                         check_vma=False)(raw, codes, q, qc, w)


@functools.partial(jax.jit, static_argnames=("mesh", "precision", "page",
                                             "k"))
def _envelope(raw, codes, q, qc, w, *, mesh, precision, page, k):
    """``token_match._envelope`` over the blocked scores."""
    s1, cos = _scores(raw, codes, q, qc, w, mesh=mesh, precision=precision)
    dp = s1.shape[-1]
    t = jax.lax.top_k(s1, page)[0][..., -1:]            # (Q, S, 1)
    band = tm.BAND_REL * jnp.abs(t) + tm.BAND_ABS
    ninf = jnp.float32(-jnp.inf)
    lo_v, lo_i = tm._shard_topk(jnp.where(s1 > t + band, cos, ninf), k, dp)
    hi_v, hi_i = tm._shard_topk(jnp.where(s1 >= t - band, cos, ninf), k, dp)
    return (jnp.where(jnp.isneginf(lo_v), -1, lo_i),
            jnp.where(jnp.isneginf(hi_v), -1, hi_i))


@functools.partial(jax.jit, static_argnames=("mesh", "precision", "page",
                                             "k"))
def _answer(raw, codes, q, qc, w, *, mesh, precision, page, k):
    """``token_match._answer`` over the blocked scores."""
    s1, cos = _scores(raw, codes, q, qc, w, mesh=mesh, precision=precision)
    dp = s1.shape[-1]
    shards = s1.shape[1]
    _, cand = jax.lax.top_k(s1, page)                   # (Q, S, page)
    c = jnp.take_along_axis(cos, cand, axis=2)
    nq = c.shape[0]
    gid = cand + (jnp.arange(shards, dtype=jnp.int32) * dp)[None, :, None]
    top, pos = jax.lax.top_k(c.reshape(nq, -1), k)
    return jnp.take_along_axis(gid.reshape(nq, -1), pos, axis=1), top
