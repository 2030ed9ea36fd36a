"""Sharded-vs-single-device search parity (dist/shard_index.py).

The pinned invariant: for ``page >= n_docs`` the doc-sharded index returns
ids AND scores bit-identical to ``VectorIndex.search`` for every engine,
every merge transport (blocking gather / ring stream) and every replica
count -- sharding and replication are throughput axes, never a quality
trade.  Multi-device cases run in a subprocess because
``--xla_force_host_platform_device_count`` must precede jax initialisation
(same pattern as test_moe.py); the replica cases force 8 devices (4 shards
x 2 replicas).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import TrimFilter, VectorIndex
from repro.launch.mesh import make_shard_mesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(n_docs=123, n_features=16, n_queries=7, seed=0):
    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n_docs, n_features)).astype(np.float32)
    Q = rng.normal(size=(n_queries, n_features)).astype(np.float32)
    return VectorIndex.build(V), Q


@pytest.mark.parametrize("engine", ["postings", "codes", "onehot",
                                    "codes_pallas", "fused", "fused_int8"])
def test_single_shard_is_identity(engine):
    """ns=1 runs in-process: one shard must already be bit-identical."""
    idx, Q = _build()
    sidx = idx.shard(make_shard_mesh(1))
    ids1, s1 = idx.search(Q, k=10, page=300, engine=engine)
    ids2, s2 = sidx.search(Q, k=10, page=300, engine=engine)
    assert np.array_equal(np.asarray(ids1), np.asarray(ids2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


def test_single_shard_trimmed_small_page():
    """Approximate regime smoke: trim + page < n_docs stays well-formed."""
    idx, Q = _build()
    sidx = idx.shard(make_shard_mesh(1))
    ids, scores = sidx.search(Q, k=5, page=32, trim=TrimFilter(0.05),
                              engine="codes")
    assert ids.shape == (7, 5)
    assert np.isfinite(np.asarray(scores)).all()
    assert (np.asarray(ids) >= 0).all() and (np.asarray(ids) < 123).all()


def _run_subprocess(script: str) -> None:
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=_REPO)
    assert "OK" in out.stdout, out.stdout + out.stderr


def _prelude(n_devices=4):
    return rf"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
import jax, jax.numpy as jnp, numpy as np
from repro.core import VectorIndex
from repro.launch.mesh import make_shard_mesh

def build(n_docs, n_features=16, n_queries=7, seed=0):
    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n_docs, n_features)).astype(np.float32)
    Q = rng.normal(size=(n_queries, n_features)).astype(np.float32)
    return VectorIndex.build(V), Q
"""


_PRELUDE = _prelude(4)


def test_four_shard_parity_all_engines():
    """4-device mesh, ragged (123 % 4 != 0) AND even (120 % 4 == 0) splits:
    ids/scores bit-identical for every engine (the fused and quantized
    phase-1 paths included) at page >= n_docs."""
    _run_subprocess(_PRELUDE + r"""
for n_docs in (123, 120):
    idx, Q = build(n_docs)
    sidx = idx.shard(make_shard_mesh(4))
    assert sidx.n_shards == 4 and sidx.n_docs == n_docs
    for engine in ("postings", "codes", "onehot", "codes_pallas",
                   "fused", "fused_int8"):
        ids1, s1 = idx.search(Q, k=10, page=2 * n_docs, engine=engine)
        ids2, s2 = sidx.search(Q, k=10, page=2 * n_docs, engine=engine)
        assert np.array_equal(np.asarray(ids1), np.asarray(ids2)), \
            (n_docs, engine)
        assert np.array_equal(np.asarray(s1), np.asarray(s2)), \
            (n_docs, engine)
print("OK")
""")


def test_four_shard_weighting_and_self_retrieval():
    """Global-psum idf == single-device idf; count weighting too; querying
    an indexed doc returns itself first (score 1.0) through the merge."""
    _run_subprocess(_PRELUDE + r"""
idx, _ = build(123)
sidx = idx.shard(make_shard_mesh(4))
V = np.asarray(idx.vectors)
for weighting in ("idf", "count"):
    ids1, s1 = idx.search(V[:9], k=10, page=200, weighting=weighting)
    ids2, s2 = sidx.search(V[:9], k=10, page=200, weighting=weighting)
    assert np.array_equal(np.asarray(ids1), np.asarray(ids2)), weighting
    assert np.array_equal(np.asarray(s1), np.asarray(s2)), weighting
assert (np.asarray(ids2)[:, 0] == np.arange(9)).all()
np.testing.assert_allclose(np.asarray(s2)[:, 0], 1.0, rtol=1e-5)
print("OK")
""")


def test_single_shard_stream_merge_is_identity():
    """S=1 runs in-process: the stream transport degenerates to a sort +
    self-psum and must already be bit-identical to the gather path."""
    idx, Q = _build()
    sidx = idx.shard(make_shard_mesh(1))
    ids1, s1 = idx.search(Q, k=10, page=300, engine="codes")
    ids2, s2 = sidx.search(Q, k=10, page=300, engine="codes", merge="stream")
    assert np.array_equal(np.asarray(ids1), np.asarray(ids2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


def test_unknown_merge_transport_rejected():
    idx, Q = _build()
    sidx = idx.shard(make_shard_mesh(1))
    with pytest.raises(ValueError, match="merge transport"):
        sidx.search(Q, merge="scatter")


def test_replica_parity_all_engines():
    """4 shards x 2 replicas on an 8-device (data, replica) mesh, ragged
    (123 % 4 != 0) AND even (120 % 4 == 0) splits: ids/scores bit-identical
    to the single-device index for every engine and both merge transports,
    at page >= n_docs.  n_queries=7 is odd, so the round-robin split across
    2 replica groups also exercises the query zero-pad + slice path."""
    _run_subprocess(_prelude(8) + r"""
for n_docs in (123, 120):
    idx, Q = build(n_docs)
    sidx = idx.shard(make_shard_mesh(4, 2))
    assert sidx.n_shards == 4 and sidx.n_replicas == 2
    assert sidx.n_docs == n_docs
    for engine in ("postings", "codes", "onehot", "codes_pallas",
                   "fused", "fused_int8"):
        ids1, s1 = idx.search(Q, k=10, page=2 * n_docs, engine=engine)
        for merge in ("gather", "stream"):
            ids2, s2 = sidx.search(Q, k=10, page=2 * n_docs, engine=engine,
                                   merge=merge)
            assert np.array_equal(np.asarray(ids1), np.asarray(ids2)), \
                (n_docs, engine, merge)
            assert np.array_equal(np.asarray(s1), np.asarray(s2)), \
                (n_docs, engine, merge)
print("OK")
""")


def test_replica_round_robin_and_stream_merge_invariants():
    """Replica-group round-robin is invisible to callers: every batch size
    0 < Q <= 8 (even, odd, and Q < R) returns the R=1 mesh's results
    bit-exactly, with the stream transport, on a 2x4 mesh (ragged corpus).
    Also pins the merged stream path for page < n_docs (approximate
    regime): well-formed ids/scores, no -inf leakage from pre-merge
    placeholder rows."""
    _run_subprocess(_prelude(8) + r"""
idx, Q = build(123, n_queries=8)
base = idx.shard(make_shard_mesh(4, 1))
sidx = idx.shard(make_shard_mesh(2, 4))
for nq in range(1, 9):
    ids1, s1 = base.search(Q[:nq], k=10, page=300, engine="codes")
    ids2, s2 = sidx.search(Q[:nq], k=10, page=300, engine="codes",
                           merge="stream")
    assert ids2.shape == (nq, 10), nq
    assert np.array_equal(np.asarray(ids1), np.asarray(ids2)), nq
    assert np.array_equal(np.asarray(s1), np.asarray(s2)), nq

ids, scores = sidx.search(Q, k=5, page=16, engine="codes", merge="stream")
assert ids.shape == (8, 5)
assert np.isfinite(np.asarray(scores)).all()
assert (np.asarray(ids) >= 0).all() and (np.asarray(ids) < 123).all()
print("OK")
""")


def test_batched_engine_serves_sharded_index():
    """BatchedSearchEngine fronting a doc-sharded index: the third engine of
    the parity triangle (engine results == sharded == single-device).  The
    replicated mesh with the stream transport must serve the same bits --
    the whole replica tier is invisible behind the batcher."""
    _run_subprocess(_prelude(8) + r"""
from repro.serve.engine import BatchedSearchEngine

idx, _ = build(123)
V = np.asarray(idx.vectors)
gold_ids, gold_s = idx.search(V[:8], k=5, page=300, trim=None, engine="codes")
for mesh, merge in ((make_shard_mesh(4), None),
                    (make_shard_mesh(4, 2), "stream")):
    sidx = idx.shard(mesh)
    eng = BatchedSearchEngine(sidx, batch_size=4, k=5, page=300, trim=None,
                              engine="codes", merge=merge)
    try:
        futs = [eng.submit(V[i]) for i in range(8)]
        for i, f in enumerate(futs):
            ids, scores = f.result(timeout=60)
            assert ids[0] == i, (merge, i, ids)
            assert np.array_equal(ids, np.asarray(gold_ids)[i]), (merge, i)
            assert np.array_equal(scores, np.asarray(gold_s)[i]), (merge, i)
    finally:
        eng.close()
print("OK")
""")


@pytest.mark.parametrize("n_features", [400, 768])
@pytest.mark.parametrize("filters", ["none", "trim", "trim+best"])
def test_query_encode_program_equals_op_by_op_encode(n_features, filters):
    """Both indexes encode their queries as one jitted program; its unit
    queries, codes and mask are the op-by-op encode's, bit for bit, over
    random rows of every scale and rows on the bucket edges."""
    import jax.numpy as jnp

    from repro.core import (BestFilter, CombinedEncoder, IntervalEncoder,
                            RoundingEncoder)
    from repro.core.filtering import expand_mask, feature_mask
    from repro.core.rerank import normalize
    from repro.core.search import encode_query_rows

    enc = CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1))
    trim = None if filters == "none" else TrimFilter(0.05)
    best = BestFilter(90) if filters == "trim+best" else None
    rng = np.random.default_rng(n_features)
    rows = [rng.normal(size=(16, n_features)) * s for s in (1e-3, 1.0, 50.0)]
    # unit rows whose features sit on and beside the 0.1 bucket edges
    edge = np.zeros((8, n_features))
    edge[:, :40] = np.linspace(-1.0, 1.0, 21).repeat(2)[:40] / 5.0
    rows.append(edge + rng.normal(size=edge.shape) * 1e-8)
    for x in rows:
        q = jnp.asarray(x, jnp.float32)
        want_q = normalize(q)
        want_c = enc.encode(want_q)
        want_m = expand_mask(feature_mask(want_q, trim=trim, best=best),
                             want_c.shape[-1])
        got = encode_query_rows(q, encoder=enc, trim=trim, best=best)
        for a, b in zip(got, (want_q, want_c, want_m)):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(np.asarray(a), np.asarray(b))
