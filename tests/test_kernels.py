"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.core.encoding import CombinedEncoder, IntervalEncoder, RoundingEncoder
from repro.core.quantize import quantize_rows, quantized_scores
from repro.core.rerank import normalize
from repro.kernels.bucketize import ops as bk_ops
from repro.kernels.bucketize.ref import bucketize_ref
from repro.kernels.code_match import ops as cm_ops
from repro.kernels.code_match.ref import code_match_ref
from repro.kernels.fused_phase1 import ops as fp_ops
from repro.kernels.fused_phase1.ref import (fused_phase1_quant_ref,
                                            fused_phase1_ref, match_scores)
from repro.kernels.rerank_topk import ops as rk_ops
from repro.kernels.rerank_topk.ref import rerank_scores_ref


class TestCodeMatchKernel:
    @pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32])
    @pytest.mark.parametrize("shape", [(64, 1, 8), (200, 3, 100), (512, 8, 128),
                                       (700, 5, 96), (1024, 2, 17)])
    def test_shapes_dtypes(self, dtype, shape):
        d, q, c = shape
        rng = np.random.default_rng(d + q + c)
        hi = min(100, np.iinfo(dtype).max)
        D = rng.integers(-hi, hi, size=(d, c)).astype(dtype)
        Q = rng.integers(-hi, hi, size=(q, c)).astype(dtype)
        W = rng.random((q, c)).astype(np.float32)
        got = cm_ops.code_match(jnp.asarray(D), jnp.asarray(Q), jnp.asarray(W),
                                force_pallas=True)
        want = code_match_ref(jnp.asarray(D), jnp.asarray(Q), jnp.asarray(W))
        assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    def test_block_shape_invariance(self):
        rng = np.random.default_rng(0)
        D = rng.integers(-50, 50, size=(300, 64)).astype(np.int8)
        Q = rng.integers(-50, 50, size=(4, 64)).astype(np.int8)
        W = rng.random((4, 64)).astype(np.float32)
        outs = []
        for bq, bd, bc in [(2, 128, 32), (4, 64, 64), (1, 256, 128)]:
            outs.append(np.asarray(cm_ops.code_match(
                jnp.asarray(D), jnp.asarray(Q), jnp.asarray(W),
                block_q=bq, block_d=bd, block_c=bc, force_pallas=True)))
        for o in outs[1:]:
            assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000))
    def test_property_random(self, seed):
        rng = np.random.default_rng(seed)
        d = int(rng.integers(1, 80))
        q = int(rng.integers(1, 5))
        c = int(rng.integers(1, 40))
        D = rng.integers(-10, 10, size=(d, c)).astype(np.int8)
        Q = rng.integers(-10, 10, size=(q, c)).astype(np.int8)
        W = rng.random((q, c)).astype(np.float32)
        got = cm_ops.code_match(jnp.asarray(D), jnp.asarray(Q), jnp.asarray(W),
                                force_pallas=True)
        want = code_match_ref(jnp.asarray(D), jnp.asarray(Q), jnp.asarray(W))
        assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    def test_self_match_upper_bound(self):
        """A doc matched against itself scores the full weight sum."""
        rng = np.random.default_rng(5)
        D = rng.integers(-20, 20, size=(32, 24)).astype(np.int8)
        W = rng.random((32, 24)).astype(np.float32)
        got = np.asarray(cm_ops.code_match(
            jnp.asarray(D), jnp.asarray(D), jnp.asarray(W), force_pallas=True))
        assert_allclose(np.diag(got), W.sum(-1), rtol=1e-5)
        assert (got <= W.sum(-1)[:, None] + 1e-5).all()


class TestRerankKernel:
    @pytest.mark.parametrize("shape", [(1, 16, 8), (3, 300, 64), (8, 512, 400),
                                       (2, 77, 33)])
    def test_shapes(self, shape):
        q, p, n = shape
        rng = np.random.default_rng(sum(shape))
        CV = rng.normal(size=(q, p, n)).astype(np.float32)
        QV = rng.normal(size=(q, n)).astype(np.float32)
        got = rk_ops.rerank_scores(jnp.asarray(CV), jnp.asarray(QV), force_pallas=True)
        want = rerank_scores_ref(jnp.asarray(CV), jnp.asarray(QV))
        # atol covers f32 accumulation-order drift between the blocked pallas
        # loop and the XLA einsum at n=400 (observed max ~2e-5)
        assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=5e-5)

    def test_topk_wrapper_matches_core(self):
        from repro.core.rerank import rerank_topk as core_rerank
        rng = np.random.default_rng(1)
        V = normalize(jnp.asarray(rng.normal(size=(200, 32)).astype(np.float32)))
        ids = jnp.asarray(rng.integers(0, 200, size=(4, 64)).astype(np.int32))
        Q = normalize(jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32)))
        i1, s1 = rk_ops.rerank_topk(V, ids, Q, k=5, force_pallas=True)
        i2, s2 = core_rerank(V, ids, Q, k=5)
        assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-5)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000))
    def test_property_random(self, seed):
        rng = np.random.default_rng(seed)
        q = int(rng.integers(1, 5))
        p = int(rng.integers(1, 90))
        n = int(rng.integers(1, 48))
        CV = rng.normal(size=(q, p, n)).astype(np.float32)
        QV = rng.normal(size=(q, n)).astype(np.float32)
        got = rk_ops.rerank_scores(jnp.asarray(CV), jnp.asarray(QV),
                                   force_pallas=True)
        want = rerank_scores_ref(jnp.asarray(CV), jnp.asarray(QV))
        assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                        atol=5e-5)


class TestBucketizeKernel:
    @pytest.mark.parametrize("mode,param,dtype", [
        ("round", 100.0, jnp.int8),
        ("round", 1000.0, jnp.int16),
        ("floor", 0.1, jnp.int8),
        ("floor", 0.05, jnp.int8),
    ])
    @pytest.mark.parametrize("shape", [(16, 8), (255, 40), (256, 128)])
    def test_modes(self, mode, param, dtype, shape):
        rng = np.random.default_rng(int(param) + sum(shape))
        X = rng.normal(size=shape).astype(np.float32)
        got = np.asarray(bk_ops._single(jnp.asarray(X), mode, param, dtype, 64, True))
        want = np.asarray(bucketize_ref(jnp.asarray(X), mode, param, dtype))
        # float-boundary cells may differ by 1 bucket on <0.01% of entries
        assert (got == want).mean() > 0.9999

    def test_encoder_integration(self):
        rng = np.random.default_rng(9)
        X = rng.normal(size=(100, 16)).astype(np.float32)
        for enc in [RoundingEncoder(2), IntervalEncoder(0.1), CombinedEncoder()]:
            got = np.asarray(bk_ops.encode(jnp.asarray(X), enc, force_pallas=True))
            want = np.asarray(enc.encode(normalize(jnp.asarray(X))))
            assert (got == want).mean() > 0.9999

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000))
    def test_property_random(self, seed):
        rng = np.random.default_rng(seed)
        d = int(rng.integers(1, 300))
        n = int(rng.integers(1, 64))
        mode = ["round", "floor"][int(rng.integers(0, 2))]
        param = 100.0 if mode == "round" else 0.1
        X = rng.normal(size=(d, n)).astype(np.float32)
        got = np.asarray(bk_ops._single(jnp.asarray(X), mode, param,
                                        jnp.int8, 64, True))
        want = np.asarray(bucketize_ref(jnp.asarray(X), mode, param,
                                        jnp.int8))
        assert (got == want).mean() > 0.999


# --------------------------------------------------------- fused phase-1
def _assert_fused_parity(got, want, d, ctx=""):
    """The fused fp32 contract: scores bit-equal EVERYWHERE, ids bit-equal
    wherever the score is finite, and every id in range (the -inf slots
    carry unspecified-but-clamped ids -- ops.py's contract)."""
    s_g, i_g = np.asarray(got[0]), np.asarray(got[1])
    s_w, i_w = np.asarray(want[0]), np.asarray(want[1])
    assert np.array_equal(s_g, s_w), ctx
    fin = np.isfinite(s_w)
    assert np.array_equal(i_g[fin], i_w[fin]), ctx
    assert (i_g >= 0).all() and (i_g < d).all(), ctx


class TestFusedPhase1Kernel:
    """fused_phase1 (pallas interpret + stream fallback) vs the composed
    full-matrix oracle: BIT-exact, not allclose -- the whole family shares
    ref.match_scores' fixed pairwise-tree reduction, so per-cell bits
    cannot depend on tiling."""

    @pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32])
    @pytest.mark.parametrize("shape", [(64, 1, 8, 16), (700, 5, 37, 17),
                                       (513, 8, 48, 33), (100, 1, 1, 10),
                                       (1000, 9, 20, 320)])
    def test_shapes_dtypes(self, dtype, shape):
        d, q, c, page = shape
        rng = np.random.default_rng(d + q + c + page)
        hi = min(100, np.iinfo(dtype).max)
        D = jnp.asarray(rng.integers(-hi, hi, size=(d, c)).astype(dtype))
        Q = jnp.asarray(rng.integers(-hi, hi, size=(q, c)).astype(dtype))
        W = jnp.asarray(rng.random((q, c)).astype(np.float32))
        got = fp_ops.fused_phase1(D, Q, W, page=page, force_pallas=True)
        want = fused_phase1_ref(D, Q, W, page=page)
        _assert_fused_parity(got, want, d, (shape, dtype))

    @pytest.mark.parametrize("d, q, c, block_d", [
        # the cells' widths, full query tiles, three doc tiles of two
        # 512-doc groups each, the last ragged
        (2600, 128, 800, 1024), (2600, 64, 1536, 1024),
        # code widths the tree's 8-row vregs group awkwardly: under one
        # vreg, one exactly, one and a bit, a ragged last vreg, and trees
        # whose half (1, 2, 4) is under a vreg
        (700, 8, 1, 512), (700, 8, 3, 512), (700, 8, 5, 512),
        (700, 8, 7, 512), (700, 8, 8, 512), (700, 8, 9, 512),
        (700, 8, 23, 512), (700, 8, 100, 512),
        # a batch that is not a multiple of the query tile
        (1500, 13, 40, 1024),
    ])
    def test_tree_bit_parity(self, d, q, c, block_d):
        """The kernel's depth-first tree over 8-row vregs adds the same
        pairs as ref.match_scores: scores and ids bit-equal to the
        composed oracle (computed 8 queries at a time, which cannot move
        a bit, to keep its (Q, d, C) temporary small)."""
        rng = np.random.default_rng(d + q + c)
        D = jnp.asarray(rng.integers(-4, 4, size=(d, c)).astype(np.int8))
        Q = jnp.asarray(rng.integers(-4, 4, size=(q, c)).astype(np.int8))
        W = jnp.asarray(rng.random((q, c)).astype(np.float32))
        live = jnp.asarray(rng.random(d) < 0.9)
        got = fp_ops.fused_phase1(D, Q, W, page=64, live=live,
                                  block_d=block_d, force_pallas=True)
        parts = [fused_phase1_ref(D, Q[k:k + 8], W[k:k + 8], page=64,
                                  live=live) for k in range(0, q, 8)]
        want = tuple(jnp.concatenate(p) for p in zip(*parts))
        _assert_fused_parity(got, want, d, (d, q, c, block_d))

    def test_auto_path_matches_ref_and_pallas(self):
        """The public wrapper's automatic backend choice (interpret for
        small problems, the lax.scan stream past the element limit) is
        invisible: both routes bit-match the oracle.  (5001, 9, 100)
        crosses the 2^22 limit -> stream; (5001, 9, 23) stays interpret
        and is the historical shape where a jnp.sum-based tile scorer
        diverged in the last ulp."""
        for d, q, c, page in [(5001, 9, 100, 64), (5001, 9, 23, 33),
                              (300, 4, 17, 40)]:
            rng = np.random.default_rng(d + c)
            D = jnp.asarray(rng.integers(-50, 50, size=(d, c)).astype(np.int16))
            Q = jnp.asarray(rng.integers(-50, 50, size=(q, c)).astype(np.int16))
            W = jnp.asarray(rng.random((q, c)).astype(np.float32))
            want = fused_phase1_ref(D, Q, W, page=page)
            auto = fp_ops.fused_phase1(D, Q, W, page=page)
            _assert_fused_parity(auto, want, d, ("auto", d, c))
            forced = fp_ops.fused_phase1(D, Q, W, page=page,
                                         force_pallas=True)
            _assert_fused_parity(forced, want, d, ("pallas", d, c))

    def test_live_mask_and_inf_slots(self):
        """Fewer live docs than page: the finite prefix is exactly the
        live docs' ranking, dead slots report -inf with in-range ids."""
        d, q, c, page = 60, 3, 12, 32
        rng = np.random.default_rng(0)
        D = jnp.asarray(rng.integers(-20, 20, size=(d, c)).astype(np.int8))
        Q = jnp.asarray(rng.integers(-20, 20, size=(q, c)).astype(np.int8))
        W = jnp.asarray(rng.random((q, c)).astype(np.float32))
        live = jnp.asarray(rng.random(d) < 0.3)
        n_live = int(np.asarray(live).sum())
        assert 0 < n_live < page
        want = fused_phase1_ref(D, Q, W, page=page, live=live)
        for force in (False, True):
            got = fp_ops.fused_phase1(D, Q, W, page=page, live=live,
                                      force_pallas=force)
            _assert_fused_parity(got, want, d, ("live", force))
            s = np.asarray(got[0])
            assert (np.isfinite(s).sum(axis=1) == n_live).all()
            ids_fin = np.asarray(got[1])[np.isfinite(s)]
            assert np.asarray(live)[ids_fin].all()

    def test_block_shape_invariance(self):
        """Retuning (block_q, block_d) can never move a bit."""
        rng = np.random.default_rng(1)
        D = jnp.asarray(rng.integers(-50, 50, size=(300, 64)).astype(np.int8))
        Q = jnp.asarray(rng.integers(-50, 50, size=(4, 64)).astype(np.int8))
        W = jnp.asarray(rng.random((4, 64)).astype(np.float32))
        outs = [fp_ops.fused_phase1(D, Q, W, page=33, block_q=bq,
                                    block_d=bd, force_pallas=True)
                for bq, bd in [(2, 128), (4, 64), (1, 256), (8, 512)]]
        for o in outs[1:]:
            _assert_fused_parity(o, outs[0], 300, "block invariance")

    @pytest.mark.parametrize("block_d", [8, 24, 128])
    def test_ties_across_tiles_keep_lowest_ids(self, block_d):
        """Every live doc ties: the kernel's fold must keep the lowest
        live ids, as one stable top-k over the dense matrix does, however
        the ties fall across doc tiles."""
        d, q, c, page = 100, 3, 6, 20
        D = jnp.zeros((d, c), jnp.int8)
        Q = jnp.zeros((q, c), jnp.int8)
        W = jnp.ones((q, c), jnp.float32)
        live = jnp.asarray(np.arange(d) % 3 != 1)
        want = fused_phase1_ref(D, Q, W, page=page, live=live)
        got = fp_ops.fused_phase1(D, Q, W, page=page, live=live,
                                  block_d=block_d, force_pallas=True)
        _assert_fused_parity(got, want, d, block_d)
        live_ids = np.flatnonzero(np.asarray(live))[:page]
        assert (np.asarray(got[1]) == live_ids[None, :]).all()

    def test_match_scores_doc_tile_invariance(self):
        """The load-bearing property underneath everything: scoring a doc
        slice yields the SAME bits as slicing the full score matrix, for
        awkward odd split points too."""
        rng = np.random.default_rng(2)
        d, q, c = 301, 4, 23
        D = jnp.asarray(rng.integers(-30, 30, size=(d, c)).astype(np.int16))
        Q = jnp.asarray(rng.integers(-30, 30, size=(q, c)).astype(np.int16))
        W = jnp.asarray(rng.random((q, c)).astype(np.float32))
        full = np.asarray(match_scores(D, Q, W))
        for cut in (1, 37, 128, 300):
            lo = np.asarray(match_scores(D[:cut], Q, W))
            hi = np.asarray(match_scores(D[cut:], Q, W))
            assert np.array_equal(np.concatenate([lo, hi], axis=1), full), cut

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000))
    def test_property_random(self, seed):
        rng = np.random.default_rng(seed)
        d = int(rng.integers(1, 400))
        q = int(rng.integers(1, 5))
        c = int(rng.integers(1, 40))
        page = int(rng.integers(1, 64))
        dtype = [np.int8, np.int16, np.int32][int(rng.integers(0, 3))]
        D = jnp.asarray(rng.integers(-10, 10, size=(d, c)).astype(dtype))
        Q = jnp.asarray(rng.integers(-10, 10, size=(q, c)).astype(dtype))
        W = jnp.asarray(rng.random((q, c)).astype(np.float32))
        live = jnp.asarray(rng.random(d) < 0.8) if rng.random() < 0.5 \
            else None
        want = fused_phase1_ref(D, Q, W, page=page, live=live)
        for force in (False, True):
            got = fp_ops.fused_phase1(D, Q, W, page=page, live=live,
                                      force_pallas=force)
            _assert_fused_parity(got, want, d, (seed, force))


def _assert_quant_parity(got, want, d, ctx="", tol=1e-4):
    """The fused int8 contract: positional scores within float tolerance
    of the composed quantized reference (the blocked dot and the full
    einsum may differ in the last ulp), ids bit-equal wherever the
    reference score is separated from its neighbours by more than the
    tolerance (a last-ulp wobble may swap near-ties, never a real
    ranking), all ids in range."""
    s_g, i_g = np.asarray(got[0]), np.asarray(got[1])
    s_w, i_w = np.asarray(want[0]), np.asarray(want[1])
    fin = np.isfinite(s_w)
    assert np.array_equal(fin, np.isfinite(s_g)), ctx
    assert_allclose(s_g[fin], s_w[fin], rtol=1e-5, atol=tol, err_msg=str(ctx))
    sep = fin.copy()
    if s_w.shape[1] > 1:
        with np.errstate(invalid="ignore"):   # -inf slots: nan gap = no tie
            tie = np.abs(s_w[:, :-1] - s_w[:, 1:]) <= tol
        sep[:, 1:] &= ~tie
        sep[:, :-1] &= ~tie
    assert np.array_equal(i_g[sep], i_w[sep]), ctx
    assert (i_g >= 0).all() and (i_g < d).all(), ctx


class TestFusedPhase1QuantKernel:
    """fused_phase1_quant vs the composed quantized_scores + top_k oracle.
    int8 phase-1 is candidate selection only (callers always rescore the
    page exact fp32), so the pin is float-tolerance scores + ranking
    agreement away from ties, not bit equality."""

    @staticmethod
    def _mk(d, n, q, seed):
        rng = np.random.default_rng(seed)
        V = rng.normal(size=(d, n)).astype(np.float32) * \
            rng.uniform(0.1, 4.0, size=(d, 1)).astype(np.float32)
        codes, scale, zero = quantize_rows(jnp.asarray(V))
        Q = jnp.asarray(rng.normal(size=(q, n)).astype(np.float32))
        return jnp.asarray(V), codes, scale, zero, Q, rng

    @pytest.mark.parametrize("shape", [(64, 8, 1, 16), (300, 16, 4, 33),
                                       (513, 32, 8, 64), (100, 1, 2, 10)])
    def test_shapes(self, shape):
        d, n, q, page = shape
        _, codes, scale, zero, Q, _ = self._mk(d, n, q, sum(shape))
        got = fp_ops.fused_phase1_quant(codes, scale, zero, Q, page=page,
                                        force_pallas=True)
        want = fused_phase1_quant_ref(codes, scale, zero, Q, page=page)
        _assert_quant_parity(got, want, d, shape)

    def test_stream_path_matches_ref(self):
        """(20000, 32, 9) crosses the interpret element limit -> the
        lax.scan stream serves; same contract as the kernel path."""
        d, n, q, page = 20_000, 32, 9, 64
        _, codes, scale, zero, Q, _ = self._mk(d, n, q, 3)
        got = fp_ops.fused_phase1_quant(codes, scale, zero, Q, page=page)
        want = fused_phase1_quant_ref(codes, scale, zero, Q, page=page)
        _assert_quant_parity(got, want, d, "stream")

    def test_live_mask(self):
        d, n, q, page = 90, 12, 3, 48
        _, codes, scale, zero, Q, rng = self._mk(d, n, q, 4)
        live = jnp.asarray(rng.random(d) < 0.3)
        n_live = int(np.asarray(live).sum())
        assert 0 < n_live < page
        got = fp_ops.fused_phase1_quant(codes, scale, zero, Q, page=page,
                                        live=live, force_pallas=True)
        want = fused_phase1_quant_ref(codes, scale, zero, Q, page=page,
                                      live=live)
        _assert_quant_parity(got, want, d, "live")
        assert (np.isfinite(np.asarray(got[0])).sum(axis=1) == n_live).all()

    def test_scores_match_dequantized_oracle(self):
        """quantized_scores' factored form (scale * (q.a) + zero * sum(a))
        IS the dot against the dequantized rows -- algebra, checked to
        float tolerance."""
        V, codes, scale, zero, Q, _ = self._mk(200, 24, 4, 5)
        from repro.core.quantize import dequantize_rows
        deq = dequantize_rows(codes, scale, zero)
        want = np.asarray(Q) @ np.asarray(deq).T
        got = np.asarray(quantized_scores(codes, scale, zero, Q))
        assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000))
    def test_property_random(self, seed):
        rng = np.random.default_rng(seed)
        d = int(rng.integers(1, 300))
        n = int(rng.integers(1, 32))
        q = int(rng.integers(1, 4))
        page = int(rng.integers(1, 32))
        _, codes, scale, zero, Q, _ = self._mk(d, n, q, seed)
        want = fused_phase1_quant_ref(codes, scale, zero, Q, page=page)
        for force in (False, True):
            got = fp_ops.fused_phase1_quant(codes, scale, zero, Q,
                                            page=page, force_pallas=force)
            _assert_quant_parity(got, want, d, (seed, force))
