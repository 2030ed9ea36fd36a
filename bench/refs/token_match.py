"""Plain reference of the paper's two-phase token-match search.

Written from the paper's description and the configuration's stated
semantics, in plain ``jax.numpy``; it imports nothing of the program and
takes nothing the program made.  It makes the corpus again from the seed
(``bench/corpus.py``) and computes, for a sample of served queries:

1. unit rows, and each feature's tokens: ``round(x * 10**p)`` (half away
   from zero) and ``floor(x / w)``, one column each;
2. the query's trim mask ``|x_j| >= trim`` over both of a feature's columns;
3. idf weights ``ln(1 + (N - df + 0.5) / (df + 0.5))`` of the query's
   tokens, ``df`` counted over the whole corpus;
4. phase 1 per doc shard: the idf-weighted count of shared tokens, and the
   shard's ``page``-th best score ``t``;
5. phase 2: exact cosines.

The program sums phase-1 scores in another order than this reference, so
two docs whose scores lie within rounding of each other may order either
way.  The reference therefore gives an envelope, not one answer: ``lo`` is
the exact top-k among docs whose phase-1 score lies above ``t + band`` of
their shard (every correct program has them among its candidates), ``hi``
the top-k among docs at or above ``t - band`` (no correct program has any
other candidate).  A correct answer's sorted exact cosines lie between the
two, rank by rank.

:func:`answer` runs the same reference as a program would (per shard the
top ``page`` by phase-1 score, ties to the lower id, then the k best
cosines).  Computed in ``bfloat16`` it is the control the comparison must
refuse.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

# phase-1 scores within this band of a shard's threshold may order either
# way: 3e-5 relative covers f32 sums over 800 columns in any order and the
# f32 idf of either side, 1e-4 absolute a doc feature that rounds into the
# neighbouring bucket and moves its token's df by one
BAND_REL = 3e-5
BAND_ABS = 1e-4
# a query feature this close (absolute, on the unit vector) to a bucket
# edge or the trim threshold may encode either way in f32: such queries
# are left out of the comparison and counted
AMBIGUOUS = 1e-6
_CHUNK = 16          # queries per reference step


def _dt(precision: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]


def _tree_sum(x):
    """Sum the last axis as a pairwise tree (zero-padded to a power of 2)."""
    n = x.shape[-1]
    p2 = 1 << max(n - 1, 0).bit_length()
    if p2 != n:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, p2 - n),))
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def _unit(x):
    return x / jnp.maximum(jnp.sqrt(_tree_sum(x * x))[..., None], 1e-12)


def _tokens(x, precision, width):
    v = x * (10 ** precision)
    r = jnp.sign(v) * jnp.floor(jnp.abs(v) + 0.5)
    i = jnp.floor(x / width)
    return jnp.concatenate([r, i], axis=-1).astype(jnp.int32)


def _bucket_range(cfg) -> int:
    enc = cfg["encoder"]
    return max(10 ** enc["rounding_precision"],
               int(math.ceil(1.0 / enc["interval_width"])) + 1)


class Reference:
    """The corpus of one seed, tokenised on the chips, and its token
    document frequencies."""

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
        x = corpus.corpus(seed, self.n, cfg["n_features"], mix["n_topics"],
                          mix["noise"], self.mesh)
        self.raw = x
        self.unit, self.codes, self.hist = _prepare(
            x, precision=precision, rp=cfg["encoder"]["rounding_precision"],
            width=cfg["encoder"]["interval_width"], nb=_bucket_range(cfg))

    # ------------------------------------------------------------ queries
    def _query_side(self, queries):
        cfg, dt = self.cfg, _dt(self.precision)
        q = _unit(jnp.asarray(queries, jnp.float32).astype(dt))
        qc = _tokens(q, cfg["encoder"]["rounding_precision"],
                     cfg["encoder"]["interval_width"])
        keep = jnp.abs(q) >= cfg["trim"]
        mask = jnp.concatenate([keep, keep], axis=-1)
        nb = _bucket_range(cfg)
        df = self.hist[jnp.arange(qc.shape[-1])[None, :], qc + nb]
        df = df.astype(dt)
        w = jnp.log1p((jnp.asarray(self.n, dt) - df + 0.5) / (df + 0.5))
        return q, qc, jnp.where(mask, w, jnp.zeros((), dt))

    def _chunks(self, queries):
        for lo in range(0, len(queries), _CHUNK):
            part = np.asarray(queries[lo:lo + _CHUNK], np.float32)
            pad = _CHUNK - len(part)
            if pad:
                part = np.concatenate(
                    [part, np.repeat(part[-1:], pad, axis=0)])
            yield len(part) - pad, self._query_side(part)

    def envelope(self, queries):
        """-> (ids_lo, ids_hi), each (Q, k): the k best exact cosines among
        the docs every correct program must / may hold as candidates
        (-1 where fewer than k docs qualify)."""
        cfg = self.cfg
        lo_all, hi_all = [], []
        for n, (q, qc, w) in self._chunks(queries):
            lo, hi = _envelope(self.unit, self.codes, q, qc, w,
                               shards=self.shards, page=cfg["page"],
                               k=cfg["k"])
            lo_all.append(np.asarray(lo)[:n])
            hi_all.append(np.asarray(hi)[:n])
        return np.concatenate(lo_all), np.concatenate(hi_all)

    def answer(self, queries):
        """-> (ids, scores), each (Q, k): the reference run as the program
        would be, in this reference's precision."""
        cfg = self.cfg
        ids_all, sc_all = [], []
        for n, (q, qc, w) in self._chunks(queries):
            ids, sc = _answer(self.unit, self.codes, q, qc, w,
                              shards=self.shards, page=cfg["page"],
                              k=cfg["k"])
            ids_all.append(np.asarray(ids)[:n])
            sc_all.append(np.asarray(sc, np.float32)[:n])
        return np.concatenate(ids_all), np.concatenate(sc_all)

    def cosines(self, queries, ids) -> np.ndarray:
        """float64 cosines of the given doc ids (Q, m); -inf where id < 0."""
        ids = np.asarray(ids)
        rows = self.rows(np.clip(ids.reshape(-1), 0, self.n - 1))
        rows = rows.reshape(ids.shape + (-1,))
        rows /= np.linalg.norm(rows, axis=-1, keepdims=True)
        q = np.asarray(queries, np.float64)
        q = q / np.linalg.norm(q, axis=-1, keepdims=True)
        cos = np.einsum("qmf,qf->qm", rows, q)
        return np.where(ids >= 0, cos, -np.inf)

    def rows(self, ids) -> np.ndarray:
        """float64 corpus rows by global id, fetched from the chip that
        holds each (no gather across chips)."""
        ids = np.asarray(ids, np.int64)
        out = np.empty((len(ids), self.cfg["n_features"]), np.float64)
        for sh in self.raw.addressable_shards:
            lo = sh.index[0].start or 0
            sel = np.nonzero((ids >= lo) & (ids < lo + sh.data.shape[0]))[0]
            if len(sel):
                out[sel] = np.asarray(_take(sh.data, jnp.asarray(
                    ids[sel] - lo, jnp.int32)), np.float64)
        return out

    def ambiguous(self, queries) -> np.ndarray:
        """(Q,) True where a query feature lies within ``AMBIGUOUS`` of the
        trim threshold, or of a bucket edge while it is kept."""
        cfg = self.cfg
        q = np.asarray(queries, np.float64)
        q = q / np.linalg.norm(q, axis=-1, keepdims=True)
        a = np.abs(q)
        step = 10.0 ** -cfg["encoder"]["rounding_precision"]
        r_edge = np.abs(np.mod(a, step) - step / 2)       # x.5 at scale
        width = cfg["encoder"]["interval_width"]
        i_edge = np.abs(q / width - np.round(q / width)) * width
        kept = a >= cfg["trim"] - AMBIGUOUS
        near = (np.abs(a - cfg["trim"]) < AMBIGUOUS) | (
            kept & ((r_edge < AMBIGUOUS) | (i_edge < AMBIGUOUS)))
        return near.any(axis=-1)


@functools.partial(jax.jit, static_argnames=("precision", "rp", "width",
                                             "nb"))
def _prepare(x, *, precision, rp, width, nb):
    u = _unit(x.astype(_dt(precision)))
    codes = _tokens(u, rp, width).astype(jnp.int8)
    hist = jnp.stack([jnp.sum(codes == b, axis=0, dtype=jnp.int32)
                      for b in range(-nb, nb + 1)], axis=-1)   # (C, 2nb+1)
    return u, codes, hist


def _phase1(codes, qc, w):
    """(Q, n) idf-weighted count of shared tokens, in w's dtype."""
    eq = codes[None, :, :] == qc.astype(codes.dtype)[:, None, :]
    return jnp.sum(jnp.where(eq, w[:, None, :], jnp.zeros((), w.dtype)),
                   axis=-1, dtype=w.dtype)


def _cos(unit, q):
    return jnp.einsum("nf,qf->qn", unit, q,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=unit.dtype)


def _shard_topk(vals, k, dp):
    """top-k of each shard of (Q, S, dp), then over shards -> (values,
    global ids), each (Q, k); ties go to the lower id."""
    q, shards, _ = vals.shape
    v, i = jax.lax.top_k(vals, k)
    i = i + (jnp.arange(shards, dtype=jnp.int32) * dp)[None, :, None]
    top, pos = jax.lax.top_k(v.reshape(q, -1), k)
    return top, jnp.take_along_axis(i.reshape(q, -1), pos, axis=1)


def _scores(unit, codes, q, qc, w, shards):
    """Phase-1 scores and cosines, each (Q, S, dp) float32."""
    nq, n = q.shape[0], codes.shape[0]
    s1 = _phase1(codes, qc, w).astype(jnp.float32)
    cos = _cos(unit, q).astype(jnp.float32)
    return (s1.reshape(nq, shards, n // shards),
            cos.reshape(nq, shards, n // shards))


@functools.partial(jax.jit, static_argnames=("shards", "page", "k"))
def _envelope(unit, codes, q, qc, w, *, shards, page, k):
    s1, cos = _scores(unit, codes, q, qc, w, shards)
    dp = s1.shape[-1]
    t = jax.lax.top_k(s1, page)[0][..., -1:]            # (Q, S, 1)
    band = BAND_REL * jnp.abs(t) + BAND_ABS
    ninf = jnp.float32(-jnp.inf)
    lo_v, lo_i = _shard_topk(jnp.where(s1 > t + band, cos, ninf), k, dp)
    hi_v, hi_i = _shard_topk(jnp.where(s1 >= t - band, cos, ninf), k, dp)
    return (jnp.where(jnp.isneginf(lo_v), -1, lo_i),
            jnp.where(jnp.isneginf(hi_v), -1, hi_i))


@functools.partial(jax.jit, static_argnames=("shards", "page", "k"))
def _answer(unit, codes, q, qc, w, *, shards, page, k):
    s1, cos = _scores(unit, codes, q, qc, w, shards)
    dp = s1.shape[-1]
    _, cand = jax.lax.top_k(s1, page)                   # (Q, S, page)
    c = jnp.take_along_axis(cos, cand, axis=2)
    q = c.shape[0]
    gid = cand + (jnp.arange(shards, dtype=jnp.int32) * dp)[None, :, None]
    top, pos = jax.lax.top_k(c.reshape(q, -1), k)
    return jnp.take_along_axis(gid.reshape(q, -1), pos, axis=1), top


@jax.jit
def _take(x, ids):
    return x[ids]
