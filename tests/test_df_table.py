"""The per-shard df table (core/postings.py, dist/shard_index.py).

The pinned invariants:

* the df table counted off the codes (``build_df_table``) is
  integer-identical to the table the posting-range lookup fills, sentinel
  entry included;
* a df table read (``table_df``) is integer-identical to the posting-range
  lookup (``df_lookup``) and to the dense count (``code_df``) for every
  code a column can be asked for: every legal bucket, the sentinel, and
  codes outside the encoder's range;
* the encoders emit query codes inside ``[-max_abs_bucket,
  max_abs_bucket]`` only, trimmed columns included (a trim masks a
  column's weight, never its code);
* every base and sealed-segment table of an index equals ``df_lookup``
  over that shard's postings and the dense count over its live rows,
  through build, delete, ingest and seal, and merge, on 1 and 4 shards;
* search answers are identical bit for bit with the table and with the
  per-query lookup it replaced, and an encoder whose code range is too
  wide for a table keeps that lookup;
* ``index.df_table.builds`` counts each table (re)build and no search;
* the base posting lists are built on demand only: a fused index holds
  none, ``index.postings.builds`` stays 0 through build and fused search
  and counts the first ``postings``-engine search, and answers do not
  depend on whether they were built -- at 400 and at 768 features.
"""

import dataclasses
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CombinedEncoder, IntervalEncoder, RoundingEncoder,
                        TrimFilter, VectorIndex)
from repro.core.filtering import expand_mask, feature_mask
from repro.core.postings import (_table_codes, build_df_table,
                                 build_postings, code_df, df_lookup,
                                 table_df)
from repro.core.rerank import normalize
from repro.core.search import _SENTINEL
from repro.dist.shard_index import ShardedVectorIndex, _put, _ROW
from repro.launch.mesh import make_shard_mesh
from repro.obs.compile_watch import CompileWatch
from repro.obs.metrics import MetricsRegistry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ENCODERS = {
    "combined_r1_i01": CombinedEncoder(RoundingEncoder(1),
                                       IntervalEncoder(0.1)),
    "rounding2": RoundingEncoder(2),
    "combined_r3_i02": CombinedEncoder(),        # int16 codes, still tabled
}
# the served encoder, at the widths of the benchmark's deployments
_COMBINED = CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1))
_WIDTHS = (400, 768)


def _postings_table(codes, max_abs_bucket, sentinel):
    """The df table as the posting-range lookup fills it: ``df_lookup``
    over the codes' sorted postings for every code the table holds."""
    vals = _table_codes(max_abs_bucket, sentinel)
    C = codes.shape[1]
    q = jnp.broadcast_to(jnp.asarray(vals, codes.dtype)[:, None],
                         (vals.size, C))
    return np.asarray(df_lookup(build_postings(codes), q)).T


def _probe_codes(encoder, n_columns, rng, n_random=64):
    """(Q, C) query codes: every legal code in every column, the
    sentinel, and random codes over the whole code dtype."""
    dt = np.dtype(encoder.code_dtype)
    m = encoder.max_abs_bucket
    legal = np.arange(-m, m + 1)
    info = np.iinfo(dt)
    rand = rng.integers(info.min, info.max, size=(n_random, n_columns),
                        endpoint=True)
    rows = [np.broadcast_to(legal[:, None], (legal.size, n_columns)),
            np.full((1, n_columns), _SENTINEL[dt]),
            np.full((1, n_columns), info.min),
            np.full((1, n_columns), m + 1),
            np.full((1, n_columns), -m - 1),
            rand]
    return np.concatenate(rows).astype(dt)


@pytest.mark.parametrize("name", sorted(_ENCODERS))
def test_table_read_equals_lookup_and_code_df(name):
    """Random codes with sentinel rows (padding, tombstones): the table read
    equals ``df_lookup`` and ``code_df`` for every probe code."""
    encoder = _ENCODERS[name]
    rng = np.random.default_rng(7)
    n_feat = 6
    V = normalize(jnp.asarray(rng.normal(size=(90, n_feat)), jnp.float32))
    codes = np.array(encoder.encode(V))
    codes[rng.random(codes.shape[0]) < 0.2] = _SENTINEL[codes.dtype]
    codes = jnp.asarray(codes)
    p = build_postings(codes)
    m, sentinel = encoder.max_abs_bucket, _SENTINEL[codes.dtype]
    table = build_df_table(codes, m, sentinel)
    assert table.shape == (codes.shape[1], 2 * m + 2)
    assert table.dtype == jnp.int32
    assert np.array_equal(np.asarray(table),
                          _postings_table(codes, m, sentinel))
    q = jnp.asarray(_probe_codes(encoder, codes.shape[1], rng))
    got = np.asarray(table_df(table, p, q, m, sentinel))
    assert np.array_equal(got, np.asarray(df_lookup(p, q)))
    assert np.array_equal(got, np.asarray(code_df(codes, q)))


@pytest.mark.parametrize("name", sorted(_ENCODERS))
def test_query_codes_stay_in_table_range(name):
    """What the encoders emit for query tokens: unit rows (axis vectors,
    near-zero and random rows) and the zero row all encode inside
    ``[-max_abs_bucket, max_abs_bucket]``; trimming changes no code."""
    encoder = _ENCODERS[name]
    n = 16
    rng = np.random.default_rng(3)
    eye = np.eye(n, dtype=np.float32)
    raw = np.concatenate([eye, -eye, np.zeros((1, n), np.float32),
                          rng.normal(size=(64, n)).astype(np.float32),
                          1e-30 * rng.normal(size=(4, n)).astype(np.float32)])
    q = normalize(jnp.asarray(raw))
    qcodes = np.asarray(encoder.encode(q)).astype(np.int64)
    m = encoder.max_abs_bucket
    assert qcodes.min() >= -m and qcodes.max() <= m
    mask = np.asarray(expand_mask(feature_mask(q, trim=TrimFilter(0.05)),
                                  qcodes.shape[-1]))
    assert (~mask).any()                         # some columns trimmed
    trimmed = qcodes[~mask]
    assert trimmed.min() >= -m and trimmed.max() <= m


def _shard_dfs(sidx, qcodes):
    """Every df table of ``sidx`` (base, then each sealed segment), per
    shard, against the table the posting-range lookup fills, df_lookup over
    its postings and the dense count over its codes (dead rows carry the
    sentinel).  The base posting lists are compared only where the index
    built them; reading them here would build them."""
    m = sidx.encoder.max_abs_bucket
    sentinel = int(_SENTINEL[sidx.codes.dtype])
    base = sidx.post_codes if sidx.has_postings else None
    parts = [(sidx.codes, base, sidx.df_table)]
    parts += [(s.codes, s.post_codes, s.df_table) for s in sidx.segments]
    q = jnp.asarray(qcodes)
    for codes, pcodes, table in parts:
        assert table.shape[-1] == 2 * m + 2
        for s in range(sidx.n_shards):
            p = build_postings(codes[s])
            if pcodes is not None:
                assert np.array_equal(np.asarray(p.post_codes),
                                      np.asarray(pcodes[s]))
            assert np.array_equal(np.asarray(table[s]), _postings_table(
                codes[s], m, sentinel))
            got = np.asarray(table_df(table[s], p, q, m, sentinel))
            assert np.array_equal(got, np.asarray(df_lookup(p, q)))
            assert np.array_equal(got, np.asarray(code_df(codes[s], q)))


def _live_df(sidx, qcodes):
    """Host count of live rows per (query, column) code: what token_df must
    read in every state."""
    rows = [np.asarray(sidx.codes)[np.asarray(sidx.live)]]
    rows += [np.asarray(s.codes)[np.asarray(s.live)] for s in sidx.segments]
    if sidx.seg_capacity:
        rows.append(np.asarray(sidx.seg_codes)[np.asarray(sidx.seg_live)])
    live = np.concatenate(rows)
    return (qcodes[:, None, :] == live[None]).sum(axis=1)


def check_lifecycle(n_shards, n_feat=8):
    """Tables exact after build, delete, ingest + seal, merge."""
    rng = np.random.default_rng(11)
    encoder = _COMBINED
    V = rng.normal(size=(45, n_feat)).astype(np.float32)
    Q = rng.normal(size=(5, n_feat)).astype(np.float32)
    sidx = ShardedVectorIndex.build_sharded(
        V, make_shard_mesh(n_shards), encoder=encoder, seal_threshold=6)
    probe = _probe_codes(encoder, sidx.codes.shape[-1], rng)
    qcodes = np.asarray(encoder.encode(normalize(jnp.asarray(Q))))

    def check(tag):
        _shard_dfs(sidx, probe)
        assert np.array_equal(np.asarray(sidx.token_df(Q)),
                              _live_df(sidx, qcodes)), tag

    check("build")
    sidx = sidx.delete([0, 3, 44])
    check("delete")
    for m in (7, 7, 3):                         # gids 45-51, 52-58 sealed
        sidx = sidx.add_documents(rng.normal(size=(m, n_feat))
                                  .astype(np.float32))
    assert sidx.n_segments == 2 and sidx.n_active == 3
    check("ingest and seal")
    sidx = sidx.delete([1, 46, 53, 60])         # base, both segments, active
    check("delete in segments")
    sidx = sidx.merge_segments()
    assert sidx.n_segments == 1
    check("merge")
    assert not sidx.has_postings        # no step needed the base's lists
    sidx = sidx.compact()
    check("compact")


def test_tables_exact_through_lifecycle_one_shard():
    check_lifecycle(1)


@pytest.mark.parametrize("n_feat", _WIDTHS)
def test_tables_exact_through_lifecycle_at_deployment_widths(n_feat):
    """The same lifecycle at 800 and 1,536 code columns: the tables
    counted off the codes equal the postings-derived ones, sentinels,
    tombstones after delete and merged segments included."""
    check_lifecycle(1, n_feat)


def _run_subprocess(script: str) -> None:
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=_REPO)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]


def test_tables_exact_through_lifecycle_four_shards():
    """4-device mesh (ragged: 45 % 4 != 0) in a subprocess -- the device
    count must be set before jax initialises."""
    _run_subprocess(
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import sys\nsys.path.insert(0, 'tests')\n"
        "from test_df_table import check_lifecycle\n"
        "check_lifecycle(4)\ncheck_lifecycle(4, 768)\nprint('OK')\n")


def _without_table(sidx):
    """The same index with width-0 tables: every df comes from the
    per-query posting-range lookup, the path the table replaced."""
    empty = lambda t: _put(sidx.mesh, t[..., :0], _ROW)
    return dataclasses.replace(
        sidx, df_table=empty(sidx.df_table),
        segments=tuple(dataclasses.replace(s, df_table=empty(s.df_table))
                       for s in sidx.segments))


@pytest.mark.parametrize("engine", ["fused", "postings"])
def test_search_identical_with_and_without_table(engine):
    rng = np.random.default_rng(5)
    n_feat = 12
    V = rng.normal(size=(70, n_feat)).astype(np.float32)
    Q = rng.normal(size=(6, n_feat)).astype(np.float32)
    sidx = ShardedVectorIndex.build_sharded(
        V, make_shard_mesh(1),
        encoder=CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1)),
        seal_threshold=4)
    sidx = sidx.add_documents(rng.normal(size=(6, n_feat))
                              .astype(np.float32)).delete([2, 71])
    assert sidx.n_segments == 1 and sidx.df_table.shape[-1] == 24
    old = _without_table(sidx)
    for page, trim in ((2 * sidx.n_ids, None), (9, TrimFilter(0.05))):
        i1, s1 = sidx.search(Q, k=5, page=page, trim=trim, engine=engine)
        i2, s2 = old.search(Q, k=5, page=page, trim=trim, engine=engine)
        assert np.array_equal(np.asarray(i1), np.asarray(i2)), page
        assert np.array_equal(np.asarray(s1), np.asarray(s2)), page


@pytest.mark.parametrize("engine", ["fused", "postings"])
def test_wide_codes_keep_the_lookup(engine):
    """RoundingEncoder(6): int32 codes, 2,000,002 entries per column, so 16
    columns would take 128 MB -- no table, df from the lookup, answers
    still identical to the single-device index."""
    enc = RoundingEncoder(6)
    rng = np.random.default_rng(9)
    V = rng.normal(size=(40, 16)).astype(np.float32)
    Q = rng.normal(size=(4, 16)).astype(np.float32)
    single = VectorIndex.build(V, encoder=enc)
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1),
                                            encoder=enc)
    assert sidx.df_table.shape == (1, 16, 0)
    i1, s1 = single.search(Q, k=5, page=80, engine=engine)
    i2, s2 = sidx.search(Q, k=5, page=80, engine=engine)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    qcodes = np.asarray(enc.encode(normalize(jnp.asarray(Q))))
    assert np.array_equal(np.asarray(sidx.token_df(Q)),
                          _live_df(sidx, qcodes))


def test_builds_counter_counts_rebuilds_not_searches():
    metrics = MetricsRegistry()
    watch = CompileWatch(metrics=metrics, enabled=True)
    builds = metrics.counter("index.df_table.builds")
    rng = np.random.default_rng(2)
    V = rng.normal(size=(30, 8)).astype(np.float32)
    Q = rng.normal(size=(3, 8)).astype(np.float32)

    def step(fn, expect):
        before = builds.value
        with watch.region("test"):
            out = fn()
        assert builds.value - before == expect
        return out

    sidx = step(lambda: ShardedVectorIndex.build_sharded(
        V, make_shard_mesh(1), seal_threshold=4), 1)
    for _ in range(3):
        step(lambda: sidx.search(Q, k=3, page=10, engine="fused"), 0)
    sidx = step(lambda: sidx.delete([4]), 1)            # base refresh
    sidx = step(lambda: sidx.add_documents(V[:3]), 0)   # active buffer only
    sidx = step(lambda: sidx.add_documents(V[3:8]), 1)  # seal
    sidx = step(lambda: sidx.add_documents(V[8:12]), 1)  # second seal
    sidx = step(lambda: sidx.merge_segments(), 1)
    step(lambda: sidx.search(Q, k=3, page=10, engine="postings"), 0)
    step(lambda: sidx.token_df(Q), 0)
    wide = rng.normal(size=(30, 16)).astype(np.float32)   # too wide: no table
    step(lambda: ShardedVectorIndex.build_sharded(
        wide, make_shard_mesh(1), encoder=RoundingEncoder(6)), 0)


def _leaf_names(sidx):
    return {name for name, _, _ in sidx.resident_leaves()}


@pytest.mark.parametrize("n_feat", _WIDTHS)
def test_fused_index_builds_postings_only_on_demand(n_feat):
    """A fused build holds no base posting lists and builds none through
    build, fused search and df reads; the first ``postings``-engine search
    builds them once, and no answer depends on whether they exist."""
    metrics = MetricsRegistry()
    watch = CompileWatch(metrics=metrics, enabled=True)
    builds = metrics.counter("index.postings.builds")
    rng = np.random.default_rng(n_feat)
    V = rng.normal(size=(48, n_feat)).astype(np.float32)
    Q = rng.normal(size=(4, n_feat)).astype(np.float32)
    mesh = make_shard_mesh(1)
    trim = TrimFilter(0.05)
    with watch.region("test"):
        sidx = ShardedVectorIndex.build_sharded(V, mesh, encoder=_COMBINED)
        assert not sidx.has_postings
        assert not {"post_docs", "post_codes"} & _leaf_names(sidx)
        fused = [sidx.search(Q, k=5, page=p, trim=trim, engine="fused")
                 for p in (9, 96)]
        sidx.token_df(Q)
        assert builds.value == 0 and not sidx.has_postings
        post = sidx.search(Q, k=5, page=96, trim=trim, engine="postings")
        assert builds.value == 1 and sidx.has_postings
        assert {"post_docs", "post_codes"} <= _leaf_names(sidx)
        sidx.search(Q, k=5, page=96, trim=trim, engine="postings")
        assert 1 <= sidx.max_df <= sidx.docs_per_shard
        assert builds.value == 1                # cached per instance
        again = [sidx.search(Q, k=5, page=p, trim=trim, engine="fused")
                 for p in (9, 96)]
        # a fresh index whose lists were read before its first search
        other = ShardedVectorIndex.build_sharded(V, mesh, encoder=_COMBINED)
        p = build_postings(other.codes[0])
        assert np.array_equal(np.asarray(other.post_docs[0]),
                              np.asarray(p.post_docs))
        assert builds.value == 2
        first = [other.search(Q, k=5, page=p, trim=trim, engine="fused")
                 for p in (9, 96)]
    for a, b, c in zip(fused, again, first):
        for x, y, z in zip(a, b, c):
            assert np.array_equal(np.asarray(x), np.asarray(y))
            assert np.array_equal(np.asarray(x), np.asarray(z))
    # page >= n_docs: every engine answers the same exact top-k
    assert np.array_equal(np.asarray(post[0]), np.asarray(fused[1][0]))
    assert np.array_equal(np.asarray(post[1]), np.asarray(fused[1][1]))


def test_derived_indexes_carry_or_drop_the_postings():
    """Mutations that leave the base codes alone keep built posting lists
    (no second sort); a base delete drops them, and the lists sorted again
    on demand match the new codes.  Without a build nothing carries one."""
    metrics = MetricsRegistry()
    watch = CompileWatch(metrics=metrics, enabled=True)
    builds = metrics.counter("index.postings.builds")
    rng = np.random.default_rng(4)
    V = rng.normal(size=(40, 8)).astype(np.float32)
    with watch.region("test"):
        sidx = ShardedVectorIndex.build_sharded(
            V, make_shard_mesh(1), encoder=_COMBINED, seal_threshold=4)
        bare = sidx.add_documents(V[:5]).delete([41]).merge_segments()
        assert not bare.has_postings and builds.value == 0
        sidx.post_docs
        out = sidx.add_documents(V[:5])             # seals one segment
        assert out.n_segments == 1 and out.has_postings
        out = out.delete([41]).merge_segments()     # appended rows only
        assert out.has_postings and builds.value == 1
        out = out.delete([3])                       # a base row
        assert not out.has_postings
        p = build_postings(out.codes[0])
        assert np.array_equal(np.asarray(out.post_codes[0]),
                              np.asarray(p.post_codes))
        assert builds.value == 2
