"""Durability subsystem (repro/store): translog, commit points, recovery.

The pinned acceptance invariant: an index recovered from DISK ALONE
(latest commit point + translog replay, torn tails truncated) returns
BIT-IDENTICAL search results to the pre-kill live index -- at every
ingest/delete/compact stage boundary, for all engines at
``page >= n_docs``, on 1-, 4-, and 4x2-device meshes (multi-device in
subprocesses, the usual virtual-device pattern).  On the writer's own
mesh shape the pin is stronger: every LEAF is bit-identical, so parity
holds at any page.  Compaction pairs with a commit (the maintenance
daemon's behaviour): compaction is content-preserving but re-normalizes
vectors, so an uncommitted compact recovers to the equally-valid
pre-compact state (identical ids, last-ulp scores) -- the bit-parity
contract is over the acked op history, which is exactly what the log
holds.

Also pinned here: translog framing/torn-tail/corruption semantics,
commit fallback past a damaged newest generation, the maintenance
daemon's post-compaction commit + translog trim, ClusterEngine's
``restore_group`` (a downed group re-admitted from disk, bit-identical
to its surviving siblings), canary health probing, and the router's
stream-pin LRU eviction cap.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cluster import ClusterEngine, MaintenanceDaemon
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh
from repro.serve.engine import BatchedSearchEngine
from repro.store import (NoCommitError, Store, Translog,
                         TranslogCorruptedError, latest_commit, read_ops,
                         recover, restore, write_commit)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LEAVES = ("vectors", "codes", "post_docs", "post_codes", "df_table",
           "offsets", "live", "seg_vectors", "seg_codes", "seg_gids",
           "seg_live")
_SEG_LEAVES = ("vectors", "codes", "gids", "live", "post_docs", "post_codes",
               "df_table")
_ENGINES = ("postings", "codes", "onehot")


def _build(n_docs=30, dims=10, seed=0):
    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n_docs, dims)).astype(np.float32)
    return V, rng


def _assert_bit_identical(live, rec, queries, ctx, *, leaves=True):
    if leaves:
        for name in _LEAVES:
            a = np.asarray(getattr(live, name))
            b = np.asarray(getattr(rec, name))
            assert np.array_equal(a, b), (ctx, name)
        assert tuple(live.shard_tombstones or ()) == \
            tuple(rec.shard_tombstones or ()), ctx
        # sealed generations survive the disk round trip structurally:
        # same count, same rows/tombstones, same leaves per segment
        assert live.seg_base == rec.seg_base, ctx
        assert live.active_tombstones == rec.active_tombstones, ctx
        assert len(live.segments) == len(rec.segments), ctx
        for si, (sa, sb) in enumerate(zip(live.segments, rec.segments)):
            assert sa.n_rows == sb.n_rows, (ctx, si)
            assert sa.tombstones == sb.tombstones, (ctx, si)
            for name in _SEG_LEAVES:
                assert np.array_equal(np.asarray(getattr(sa, name)),
                                      np.asarray(getattr(sb, name))), \
                    (ctx, si, name)
    assert live.n_ids == rec.n_ids and live.n_docs == rec.n_docs, ctx
    for engine in _ENGINES:
        i1, s1 = live.search(queries, k=8, page=2 * live.n_ids,
                             engine=engine)
        i2, s2 = rec.search(queries, k=8, page=2 * rec.n_ids, engine=engine)
        assert np.array_equal(np.asarray(i1), np.asarray(i2)), (ctx, engine)
        assert np.array_equal(np.asarray(s1), np.asarray(s2)), (ctx, engine)


def test_snapshot_round_trip_of_a_postings_free_index(tmp_path):
    """An index whose base posting lists were never built commits and
    restores without building them; the restored leaves, df tables and
    fused answers equal the live index's, and the lists both sort on
    demand are equal too."""
    from repro.core import CombinedEncoder, IntervalEncoder, RoundingEncoder
    from repro.obs.compile_watch import CompileWatch
    from repro.obs.metrics import MetricsRegistry

    metrics = MetricsRegistry()
    watch = CompileWatch(metrics=metrics, enabled=True)
    builds = metrics.counter("index.postings.builds")
    V, rng = _build(n_docs=40, dims=12, seed=3)
    Q = rng.normal(size=(4, 12)).astype(np.float32)
    enc = CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1))
    with watch.region("test"):
        live = ShardedVectorIndex.build_sharded(
            V, make_shard_mesh(1), encoder=enc, seal_threshold=4)
        live = live.add_documents(rng.normal(size=(6, 12))
                                  .astype(np.float32)).delete([2, 41])
        write_commit(str(tmp_path), live, seq=0)
        rec = restore(latest_commit(str(tmp_path)), make_shard_mesh(1))
        assert not live.has_postings and not rec.has_postings
        assert builds.value == 0
        for name in _LEAVES:
            if name not in ("post_docs", "post_codes"):
                assert np.array_equal(np.asarray(getattr(live, name)),
                                      np.asarray(getattr(rec, name))), name
        for page in (9, 2 * live.n_ids):
            i1, s1 = live.search(Q, k=6, page=page, engine="fused")
            i2, s2 = rec.search(Q, k=6, page=page, engine="fused")
            assert np.array_equal(np.asarray(i1), np.asarray(i2)), page
            assert np.array_equal(np.asarray(s1), np.asarray(s2)), page
        assert builds.value == 0
        for name in ("post_docs", "post_codes"):
            assert np.array_equal(np.asarray(getattr(live, name)),
                                  np.asarray(getattr(rec, name))), name
        assert builds.value == 2


# ---------------------------------------------------------------- translog
def test_translog_append_replay_roundtrip(tmp_path):
    log = Translog(str(tmp_path))
    rng = np.random.default_rng(0)
    V = rng.normal(size=(4, 6)).astype(np.float32)
    assert log.seqno == 0
    assert log.add(V) == 1
    assert log.delete([3, 7]) == 2
    assert log.add(V[:2]) == 3
    log.close()
    ops = list(read_ops(str(tmp_path)))
    assert [s for s, _, _ in ops] == [1, 2, 3]
    assert np.array_equal(ops[0][2], V)
    assert np.array_equal(ops[1][2], np.asarray([3, 7], np.int64))
    # replay past a commit point skips covered records
    assert [s for s, _, _ in read_ops(str(tmp_path), after_seq=2)] == [3]


def test_translog_truncates_torn_tail(tmp_path):
    log = Translog(str(tmp_path))
    V = np.ones((2, 4), np.float32)
    log.add(V)
    log.add(2 * V)
    path = os.path.join(str(tmp_path), f"translog-{log.generation:08d}.log")
    log.close()
    size = os.path.getsize(path)
    with open(path, "r+b") as f:        # crash mid-append: half a record
        f.truncate(size - 7)
    ops = list(read_ops(str(tmp_path)))             # truncates as it reads
    assert [s for s, _, _ in ops] == [1]
    assert os.path.getsize(path) < size - 7
    # the repaired log accepts new appends at the right seqno
    log = Translog(str(tmp_path))
    assert log.seqno == 1 and log.add(V) == 2
    log.close()


def test_translog_corruption_mid_stream_raises(tmp_path):
    log = Translog(str(tmp_path))
    log.add(np.ones((2, 4), np.float32))
    gen1 = log.generation
    log.roll()                                      # record 1 is no longer
    log.add(np.ones((1, 4), np.float32))            # in the newest gen
    log.close()
    path = os.path.join(str(tmp_path), f"translog-{gen1:08d}.log")
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) - 3)
        f.write(b"\xff\xff\xff")
    with pytest.raises(TranslogCorruptedError, match="corrupt record"):
        list(read_ops(str(tmp_path)))


def test_translog_torn_header_artifact_never_bricks(tmp_path):
    """Crash mid-roll can leave a generation file with a partial header.
    Reopening must DELETE the artifact -- merely skipping it would brick
    the log once newer generations hold records (the torn file would no
    longer be 'newest' and every later scan would raise on it)."""
    log = Translog(str(tmp_path))
    V = np.ones((2, 4), np.float32)
    log.add(V)
    gen = log.generation
    log.close()
    torn = os.path.join(str(tmp_path), f"translog-{gen + 1:08d}.log")
    with open(torn, "wb") as f:
        f.write(b"RT")                              # header torn mid-write
    log = Translog(str(tmp_path))                   # restart: artifact is
    assert log.seqno == 1                           # deleted (the gen number
    log.add(V)                                      # is reused for a FRESH,
    log.close()                                     # valid-header file)
    assert [s for s, _, _ in read_ops(str(tmp_path))] == [1, 2]
    log = Translog(str(tmp_path))                   # and reopens fine
    assert log.seqno == 2
    log.close()


def test_translog_gap_past_commit_raises(tmp_path):
    log = Translog(str(tmp_path))
    for _ in range(3):
        log.add(np.ones((1, 4), np.float32))
        log.roll()
    log.trim(2)                                     # gens for seq 1, 2 gone
    log.close()
    assert [s for s, _, _ in read_ops(str(tmp_path), after_seq=2)] == [3]
    with pytest.raises(TranslogCorruptedError, match="gap"):
        list(read_ops(str(tmp_path), after_seq=0))  # seq 1..2 unrecoverable


def test_translog_seqno_survives_trim_and_reopen(tmp_path):
    """The base-seqno anchor: after a commit trims every record away, a
    reopened writer must continue the sequence, not restart at 1 (restart
    would alias already-committed seqnos and lose the aliased ops)."""
    log = Translog(str(tmp_path))
    for _ in range(4):
        log.add(np.ones((1, 3), np.float32))
    log.roll()
    log.trim(4)
    log.close()
    log = Translog(str(tmp_path))
    assert log.seqno == 4
    assert log.add(np.ones((1, 3), np.float32)) == 5
    log.close()


def test_translog_durability_validates(tmp_path):
    with pytest.raises(ValueError, match="durability"):
        Translog(str(tmp_path), durability="yolo")
    log = Translog(str(tmp_path), durability="async")
    log.add(np.ones((1, 3), np.float32))
    log.sync()
    log.close()
    assert len(list(read_ops(str(tmp_path)))) == 1


# ------------------------------------------------------------ commit point
def test_commit_restore_leaf_identical_same_mesh(tmp_path):
    V, rng = _build()
    Q = rng.normal(size=(4, 10)).astype(np.float32)
    mesh = make_shard_mesh(1)
    sidx = ShardedVectorIndex.build_sharded(V, mesh)
    sidx = sidx.add_documents(rng.normal(size=(5, 10)).astype(np.float32))
    sidx = sidx.delete([2, 31])
    gen = write_commit(str(tmp_path), sidx, seq=7)
    commit = latest_commit(str(tmp_path))
    assert commit.generation == gen and commit.seq == 7
    rec = restore(commit, make_shard_mesh(1))
    _assert_bit_identical(sidx, rec, Q, "commit/restore")
    assert rec.encoder == sidx.encoder and rec.index_best == sidx.index_best


def test_commit_falls_back_past_damaged_newest(tmp_path):
    V, rng = _build()
    mesh = make_shard_mesh(1)
    sidx = ShardedVectorIndex.build_sharded(V, mesh)
    write_commit(str(tmp_path), sidx, seq=1)
    grown = sidx.add_documents(rng.normal(size=(3, 10)).astype(np.float32))
    write_commit(str(tmp_path), grown, seq=2)
    # tear a blob ONLY generation 2 references (the active-buffer blob:
    # gen 1 had no appended docs) -- shared blobs must stay intact or the
    # fallback would be damaged too
    with open(os.path.join(str(tmp_path), "commit-00000002.json")) as f:
        active = json.load(f)["files"]["active"]["file"]
    with open(os.path.join(str(tmp_path), active), "r+b") as f:
        f.seek(10)
        f.write(b"\x00" * 8)
    commit = latest_commit(str(tmp_path))
    assert commit is not None and commit.seq == 1   # previous generation
    assert restore(commit, mesh).n_ids == 30


def test_commit_retention_prunes_old_generations(tmp_path):
    V, rng = _build()
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1))
    grown = sidx
    for seq in range(1, 5):
        grown = grown.add_documents(
            rng.normal(size=(2, 10)).astype(np.float32))
        write_commit(str(tmp_path), grown, seq=seq)
    names = sorted(os.listdir(str(tmp_path)))
    manifests = [n for n in names if n.startswith("commit-")]
    assert manifests == ["commit-00000003.json", "commit-00000004.json"]
    # blob GC: exactly the union of the two retained manifests' references
    # survives -- shared blobs (base vectors/state, written at gen 1) are
    # still on disk, and the pruned generations' unshared active blobs
    # are gone
    referenced = set()
    for m in manifests:
        with open(os.path.join(str(tmp_path), m)) as f:
            files = json.load(f)["files"]
        referenced |= {e["file"] for k, e in files.items()
                       if k != "segments" and e is not None}
        referenced |= {e["file"] for e in files["segments"]}
    blobs = {n for n in names if n.endswith(".seg")}
    assert blobs == referenced
    # both retained commits still fully restore
    for gen, n_ids in ((3, 36), (4, 38)):
        with open(os.path.join(str(tmp_path),
                               f"commit-{gen:08d}.json")) as f:
            assert json.load(f)["n_appended"] == n_ids - 30
    assert restore(latest_commit(str(tmp_path)),
                   make_shard_mesh(1)).n_ids == 38


def test_commit_bytes_are_o_changed(tmp_path):
    """The incremental-commit claim at the API level: a commit after a
    small ingest rewrites the changed blobs (active buffer), not the
    base vectors -- bytes_written << bytes_total on later generations."""
    V, rng = _build()
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1))
    s0: dict = {}
    write_commit(str(tmp_path), sidx, seq=1, stats=s0)
    assert s0["bytes_written"] == s0["bytes_total"]    # first commit: all new
    grown = sidx.add_documents(rng.normal(size=(2, 10)).astype(np.float32))
    s1: dict = {}
    write_commit(str(tmp_path), grown, seq=2, stats=s1)
    # base vectors + base state blobs are re-referenced, only the active
    # blob is new
    assert 0 < s1["bytes_written"] < s1["bytes_total"]
    assert s1["blobs_written"] == 1
    # identical state -> zero new bytes
    s2: dict = {}
    write_commit(str(tmp_path), grown, seq=2, stats=s2)
    assert s2["bytes_written"] == 0 and s2["blobs_written"] == 0


def test_gc_keeps_blobs_referenced_by_fallback_commit(tmp_path):
    """Retention GC must never delete a blob the FALLBACK commit
    references, even when the newest generation no longer does (a merge
    rewrote those segments).  Pinned the hard way: tear the newest
    generation's fresh blob and recover through the fallback."""
    V, rng = _build()
    mesh = make_shard_mesh(1)
    sidx = ShardedVectorIndex.build_sharded(V, mesh, seal_threshold=4)
    for _ in range(2):
        sidx = sidx.add_documents(
            rng.normal(size=(5, 10)).astype(np.float32))
    assert sidx.n_segments == 2
    write_commit(str(tmp_path), sidx, seq=1)
    with open(os.path.join(str(tmp_path), "commit-00000001.json")) as f:
        gen1_seg_blobs = {e["file"]
                          for e in json.load(f)["files"]["segments"]}
    assert gen1_seg_blobs
    merged = sidx.merge_segments()        # gen 2 references NONE of them
    write_commit(str(tmp_path), merged, seq=2)
    for blob in gen1_seg_blobs:           # GC ran; fallback blobs intact
        assert os.path.exists(os.path.join(str(tmp_path), blob)), blob
    # the fallback is not just present but USABLE: tear gen 2's merged
    # segment blob, fall back a generation, restore
    with open(os.path.join(str(tmp_path), "commit-00000002.json")) as f:
        gen2_segs = {e["file"] for e in json.load(f)["files"]["segments"]}
    target = sorted(gen2_segs - gen1_seg_blobs)[0]
    with open(os.path.join(str(tmp_path), target), "r+b") as f:
        f.seek(10)
        f.write(b"\x00" * 8)
    commit = latest_commit(str(tmp_path))
    assert commit is not None and commit.seq == 1
    assert restore(commit, mesh).n_ids == 40


def test_recover_without_commit_raises(tmp_path):
    with pytest.raises(NoCommitError):
        recover(str(tmp_path), make_shard_mesh(1))


# ------------------------------------------------- crash-recovery property
@settings(max_examples=5, deadline=None)
@given(n_docs=st.integers(8, 40), dims=st.integers(4, 12),
       n_ops=st.integers(1, 5), seed=st.integers(0, 2**20))
def test_crash_recovery_bit_parity_sweep(n_docs, dims, n_ops, seed):
    """THE property: random ingest/delete/merge/compact/commit
    interleavings, with a kill point at EVERY stage boundary -- the
    recovered index (disk state only) is bit-identical to the live
    index, leaves and search results both.  The seal threshold is tiny
    so appends routinely seal into segments and recovery replay must
    re-seal at identical boundaries.  Merge and compact pair with
    commit (daemon semantics); the no-op boundary right after the
    baseline commit is stage 0."""
    import shutil
    import tempfile

    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n_docs, dims)).astype(np.float32)
    Q = rng.normal(size=(4, dims)).astype(np.float32)
    mesh = make_shard_mesh(1)
    store_dir = tempfile.mkdtemp(prefix="repro_store_")
    store = Store(store_dir,
                  durability=["request", "async"][int(rng.integers(2))])
    live = store.open_index(
        ShardedVectorIndex.build_sharded(V, mesh, seal_threshold=4))
    if store.durability == "async":
        store.translog.sync()   # a kill is a process death, not power loss;
        #                         sync() stands in for the OS page cache
    try:
        for stage in range(n_ops + 1):
            rec, seq = recover(store_dir, make_shard_mesh(1))
            assert seq == live.translog_seq, stage
            _assert_bit_identical(live.inner, rec, Q, (seed, stage))
            if stage == n_ops:
                break
            op = rng.choice(["add", "delete", "merge", "compact"])
            if op == "add":
                m = int(rng.integers(1, 6))
                live = live.add_documents(
                    rng.normal(size=(m, dims)).astype(np.float32))
            elif op == "delete":
                ids = rng.choice(live.n_ids, size=min(3, live.n_ids),
                                 replace=False)
                live = live.delete(ids)
            elif op == "merge" and live.n_segments:
                count = int(rng.integers(1, live.n_segments + 1))
                live = live.merge_segments(0, count)
                store.commit(live)
            elif op == "compact":
                live = live.compact()
                store.commit(live)
            if rng.random() < 0.3:
                store.commit(live)                  # mid-stream commit
            if store.durability == "async":
                store.translog.sync()
    finally:
        store.close()
        shutil.rmtree(store_dir, ignore_errors=True)


# ----------------------------------------------------- engine/daemon wiring
def test_durable_index_logs_before_ack(tmp_path):
    """Write-through order: the translog seqno moves with every engine
    ingest/delete, and recovery replays exactly the acked history."""
    V, rng = _build()
    Q = rng.normal(size=(3, 10)).astype(np.float32)
    store = Store(str(tmp_path))
    idx = store.open_index(
        ShardedVectorIndex.build_sharded(V, make_shard_mesh(1)))
    eng = BatchedSearchEngine(idx, batch_size=2, trim=None, engine="codes")
    try:
        assert store.seqno == 0
        first = eng.add_documents(rng.normal(size=(4, 10)).astype(np.float32))
        assert first == 30 and store.seqno == 1
        eng.delete([1, 30])
        assert store.seqno == 2
        assert eng.index.translog_seq == 2
        rec, seq = recover(str(tmp_path), make_shard_mesh(1))
        assert seq == 2
        _assert_bit_identical(eng.index.inner, rec, Q, "engine write-through")
    finally:
        eng.close()
    store.close()


def test_failing_op_is_never_logged(tmp_path):
    """ES ordering: apply -> log -> ack.  An op that RAISES (malformed
    vectors, out-of-range id) must leave no translog record -- otherwise
    the same exception would resurface at every recovery replay and a
    single bad request would poison the store forever."""
    V, rng = _build()
    store = Store(str(tmp_path))
    idx = store.open_index(
        ShardedVectorIndex.build_sharded(V, make_shard_mesh(1)))
    with pytest.raises(ValueError, match="feature"):
        idx.add_documents(np.ones((2, 99), np.float32))  # wrong width
    with pytest.raises(ValueError, match="ids must be"):
        idx.delete([10_000])                             # out of range
    assert store.seqno == 0
    idx = idx.add_documents(rng.normal(size=(2, 10)).astype(np.float32))
    assert store.seqno == 1
    rec, seq = recover(str(tmp_path), make_shard_mesh(1))  # replay is clean
    assert seq == 1 and rec.n_ids == 32
    store.close()


def test_daemon_commits_after_compaction(tmp_path):
    """The maintenance flush: a successful compact-and-swap of a durable
    index rolls a commit point covering its translog_seq and trims the
    replayed translog -- recovery afterwards starts from the compacted
    form (bit-identical leaves, no replay needed)."""
    V, rng = _build()
    Q = rng.normal(size=(3, 10)).astype(np.float32)
    store = Store(str(tmp_path))
    idx = store.open_index(
        ShardedVectorIndex.build_sharded(V, make_shard_mesh(1)))
    eng = BatchedSearchEngine(idx, batch_size=2, trim=None, engine="codes")
    try:
        eng.delete(list(range(9)))                   # ratio 0.3 > 0.2
        daemon = MaintenanceDaemon([eng], threshold=0.2, store=store)
        assert daemon.poll_once() == 1
        assert daemon.commits == 1 and not daemon.failures
        assert eng.index.translog_seq == 1           # metadata rode the CAS
        commit = latest_commit(str(tmp_path))
        assert commit.seq == 1
        assert not list(read_ops(str(tmp_path), after_seq=commit.seq))
        rec, seq = recover(str(tmp_path), make_shard_mesh(1))
        assert seq == 1
        _assert_bit_identical(eng.index.inner, rec, Q, "daemon commit")
    finally:
        eng.close()
    store.close()


def test_merge_kill_points_recover_bit_identical(tmp_path):
    """A crash at EVERY boundary inside a background merge pass (before
    the swap installs the merged index, after the swap but before the
    commit, after the commit) recovers bit-identically.  A merge is not
    logged, so until its commit lands the acked history -- and therefore
    recovery -- names the PRE-merge layout; after the commit it names
    the merged one.  Both layouts answer searches identically, so no
    kill point can change what a recovered node serves."""
    V, rng = _build(n_docs=24)
    Q = rng.normal(size=(4, 10)).astype(np.float32)
    mesh = make_shard_mesh(1)
    store = Store(str(tmp_path))
    live = store.open_index(
        ShardedVectorIndex.build_sharded(V, mesh, seal_threshold=4))
    for _ in range(3):                       # seal three generations
        live = live.add_documents(
            rng.normal(size=(5, 10)).astype(np.float32))
    live = live.delete([30, 31, 36])         # dead rows inside segments
    assert live.n_segments >= 2
    pre = live

    # kill point 1: merge computed, crash BEFORE the swap -- nothing
    # changed on disk, recovery is the pre-merge state
    merged = pre.merge_segments(0, 2)
    rec, seq = recover(str(tmp_path), make_shard_mesh(1))
    assert seq == pre.translog_seq
    _assert_bit_identical(pre.inner, rec, Q, "before swap")

    # kill point 2: swap installed (node was serving the merged index),
    # crash BEFORE the commit -- disk still holds the pre-merge commit +
    # the full translog, so recovery reproduces the pre-merge layout
    # leaf for leaf, and that layout answers exactly like the merged one
    live = merged                            # the CAS, collapsed
    rec, seq = recover(str(tmp_path), make_shard_mesh(1))
    assert seq == live.translog_seq
    _assert_bit_identical(pre.inner, rec, Q, "after swap")
    for engine in _ENGINES:
        i1, s1 = live.search(Q, k=8, page=2 * live.n_ids, engine=engine)
        i2, s2 = rec.search(Q, k=8, page=2 * rec.n_ids, engine=engine)
        assert np.array_equal(np.asarray(i1), np.asarray(i2)), engine
        assert np.array_equal(np.asarray(s1), np.asarray(s2)), engine

    # kill point 3: crash AFTER the commit -- recovery is the merged
    # layout itself, leaf for leaf
    store.commit(live)
    rec, seq = recover(str(tmp_path), make_shard_mesh(1))
    assert seq == live.translog_seq
    _assert_bit_identical(live.inner, rec, Q, "after commit")
    store.close()


def test_cluster_restore_group_readmits_from_disk(tmp_path):
    """PR 4's dead end, closed: a replica group whose memory is poisoned
    comes back from commit + translog replay, serves bit-identically to
    its surviving sibling, and is routable again."""
    V, rng = _build()
    W = rng.normal(size=(5, 10)).astype(np.float32)
    Q = rng.normal(size=(4, 10)).astype(np.float32)
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1))
    store = Store(str(tmp_path))
    cl = ClusterEngine([sidx, sidx], batch_size=4, k=5, page=200, trim=None,
                       engine="codes", store=store)
    try:
        cl.add_documents(W)
        cl.delete([0, 31])
        ref = [cl.search(q, stream="a", timeout=60) for q in Q]
        cl.inject_failure(1)
        cl.mark_down(1)
        seq = cl.restore_group(1)
        assert seq == 2 and cl.health.is_up(1)
        got = [cl.search(q, stream="pin-b", timeout=60) for q in Q]
        for (ai, asc), (bi, bsc) in zip(ref, got):
            assert np.array_equal(ai, bi) and np.array_equal(asc, bsc)
        # group 0 (the primary) restores too, keeping write-through
        cl.mark_down(0)
        cl.restore_group(0)
        assert cl.health.is_up(0)
        first = cl.add_documents(W[:2])              # still logs: seq moves
        assert first == 35 and store.seqno == 3
    finally:
        cl.close()
    store.close()


def test_cluster_without_store_rejects_restore():
    V, _ = _build()
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1))
    cl = ClusterEngine([sidx, sidx], batch_size=2, trim=None)
    try:
        with pytest.raises(RuntimeError, match="no store attached"):
            cl.restore_group(1)
    finally:
        cl.close()


# --------------------------------------------------------- health probing
def test_probe_readmits_healed_group():
    """Background probing: a downed group stays down while its fault is
    live, and re-admits on the first canary that answers -- no manual
    mark_up, no poisoned-request rollback."""
    V, _ = _build()
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1))
    cl = ClusterEngine([sidx, sidx], batch_size=2, k=3, page=30, trim=None,
                       engine="codes")
    try:
        daemon = MaintenanceDaemon(cl.batchers, health=cl.health, probe=True)
        cl.inject_failure(1)
        cl.health.mark_down(1)          # a FAULT (what failover records)
        assert daemon.probe_once() == 0 and not cl.health.is_up(1)
        cl.heal(1)
        assert daemon.probe_once() == 1 and cl.health.is_up(1)
        assert daemon.probe_events == [{"group": 1}]
        assert daemon.probe_once() == 0              # steady state: no-op
    finally:
        cl.close()


def test_probe_respects_operator_drain():
    """cluster.mark_down is operator INTENT (a drain), not a fault: the
    prober must not re-admit a drained group however healthy its
    canaries look -- only mark_up (or restore_group) brings it back."""
    V, _ = _build()
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1))
    cl = ClusterEngine([sidx, sidx], batch_size=2, k=3, page=30, trim=None,
                       engine="codes")
    try:
        daemon = MaintenanceDaemon(cl.batchers, health=cl.health, probe=True)
        cl.mark_down(1)                 # drain: the group itself is healthy
        assert cl.health.is_drained(1)
        assert daemon.probe_once() == 0 and not cl.health.is_up(1)
        assert cl.mark_up(1)            # explicit rejoin clears the drain
        assert not cl.health.is_drained(1) and cl.health.is_up(1)
    finally:
        cl.close()


def test_probe_background_loop_readmits(tmp_path):
    """The wired path: ClusterEngine(probe_s=...) runs the prober on the
    daemon thread, so heal() alone brings the group back."""
    import time

    V, _ = _build()
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1))
    cl = ClusterEngine([sidx, sidx], batch_size=2, k=3, page=30, trim=None,
                       engine="codes", probe_s=0.01)
    try:
        assert cl.maintenance is not None and cl.maintenance.probe
        cl.inject_failure(1)
        cl.health.mark_down(1)          # fault-style mark: probe-eligible
        time.sleep(0.1)
        assert not cl.health.is_up(1)                # fault live: stays down
        cl.heal(1)
        deadline = time.monotonic() + 60
        while not cl.health.is_up(1):
            assert time.monotonic() < deadline, "prober never re-admitted"
            time.sleep(0.01)
    finally:
        cl.close()


def test_probe_requires_health():
    with pytest.raises(ValueError, match="probe"):
        MaintenanceDaemon([], probe=True)


def test_readmit_is_drain_atomic():
    """HealthMap.readmit (the prober's and failover rollback's entry
    point) must be a no-op under a drain -- even one recorded AFTER the
    fault, i.e. while a canary was already in flight -- while plain
    mark_up (the operator's explicit rejoin) clears it.  Drain mutations
    bump generation like any other cluster-state change."""
    from repro.cluster import HealthMap

    h = HealthMap(2)
    h.mark_down(1)                      # fault
    assert h.readmit(1) and h.is_up(1)  # no drain: readmit works
    h.mark_down(1)
    gen = h.generation
    assert h.mark_down(1, drain=True)   # drain lands mid-flight: changed
    assert h.generation == gen + 1      # ...and is observable via gen
    assert not h.readmit(1) and not h.is_up(1)   # canary success: ignored
    assert h.mark_up(1) and h.is_up(1) and not h.is_drained(1)
    assert not h.readmit(0)             # up group: nothing to do


def test_open_index_refuses_dirty_store(tmp_path):
    """Pairing a FRESH index with a store that already holds history
    would make recovery replay a different corpus than the one served --
    the library must refuse, pointing at recover() instead."""
    V, rng = _build()
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1))
    store = Store(str(tmp_path))
    idx = store.open_index(sidx)
    idx.add_documents(rng.normal(size=(2, 10)).astype(np.float32))
    store.close()
    store = Store(str(tmp_path))        # restart on existing history
    with pytest.raises(ValueError, match="already holds history"):
        store.open_index(sidx)
    rec, seq = store.recover(make_shard_mesh(1))    # the supported path
    assert seq == 1 and rec.translog_seq == 1
    store.close()


# ------------------------------------------------------ stream-pin LRU cap
def test_stream_pin_map_is_lru_capped():
    """The affinity map must not grow monotonically with distinct stream
    ids: past ``max_stream_pins`` the coldest pin evicts (benign -- every
    group is a bit-identical copy, an evicted stream just re-pins)."""
    V, _ = _build()
    sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(1))
    cl = ClusterEngine([sidx, sidx], batch_size=2, k=3, page=30, trim=None,
                       engine="codes", max_stream_pins=3)
    try:
        for i in range(10):
            cl.search(np.ones((10,), np.float32), stream=f"s{i}", timeout=60)
        assert len(cl._streams) == 3
        assert set(cl._streams) == {"s7", "s8", "s9"}
        cl.search(np.ones((10,), np.float32), stream="s8", timeout=60)
        cl.search(np.ones((10,), np.float32), stream="s3", timeout=60)
        assert set(cl._streams) == {"s9", "s8", "s3"}  # s8 refreshed, s7 out
    finally:
        cl.close()


# ------------------------------------------------------- multi-device pins
def _run_subprocess(script: str) -> None:
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=_REPO)
    assert "OK" in out.stdout, out.stdout + out.stderr


def test_recovery_parity_4dev_and_cross_mesh(tmp_path):
    """Kill/recover bit-parity on a real 4-shard mesh at every lifecycle
    boundary, PLUS mesh-shape freedom: the same commit restores onto 1-,
    2- and 4-shard meshes with search results bit-identical to the live
    index at page >= n_docs (the repo's mesh-parity invariant, now
    through the disk path)."""
    _run_subprocess(rf"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh
from repro.store import Store, recover

store_dir = {str(tmp_path)!r}
rng = np.random.default_rng(0)
V = rng.normal(size=(43, 10)).astype(np.float32)
Q = rng.normal(size=(4, 10)).astype(np.float32)
mesh = make_shard_mesh(4)
store = Store(store_dir)
live = store.open_index(ShardedVectorIndex.build_sharded(V, mesh))

LEAVES = ("vectors", "codes", "post_docs", "post_codes", "df_table",
          "offsets", "live", "seg_vectors", "seg_codes", "seg_gids",
          "seg_live")

def check(live, tag):
    rec, seq = recover(store_dir, make_shard_mesh(4))
    assert seq == live.translog_seq, tag
    for name in LEAVES:
        assert np.array_equal(np.asarray(getattr(live, name)),
                              np.asarray(getattr(rec, name))), (tag, name)
    for engine in ("postings", "codes", "onehot"):
        i1, s1 = live.search(Q, k=7, page=2 * live.n_ids, engine=engine)
        for shards in (1, 2, 4):
            cross, _ = recover(store_dir, make_shard_mesh(shards))
            i2, s2 = cross.search(Q, k=7, page=2 * cross.n_ids,
                                  engine=engine)
            assert np.array_equal(np.asarray(i1), np.asarray(i2)), \
                (tag, engine, shards)
            assert np.array_equal(np.asarray(s1), np.asarray(s2)), \
                (tag, engine, shards)

check(live, "built")
live = live.add_documents(rng.normal(size=(9, 10)).astype(np.float32))
check(live, "ingested")
live = live.delete([1, 17, 44, 50])
check(live, "deleted")
live = live.compact()
store.commit(live)
check(live, "compacted+committed")
live = live.add_documents(rng.normal(size=(3, 10)).astype(np.float32))
check(live, "post-compact ingest")
store.close()
print("OK")
""")


def test_restore_scatter_free_on_replica_mesh(tmp_path):
    """The replica-mesh regression (the _merge_select_seg GSPMD gotcha,
    store-path variant): a commit with LIVE APPEND SEGMENTS restores onto
    a 4x2 (data, replica) mesh -- every leaf replica-replicated -- and
    both the restored leaves and the search results match the 1-device
    reference bit for bit.  A scatter-built placement would double-count
    base rows through GSPMD's cross-replica scatter reassembly; the
    host-assembled device_put placement cannot."""
    _run_subprocess(rf"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh
from repro.store import Store, recover

store_dir = {str(tmp_path)!r}
rng = np.random.default_rng(1)
V = rng.normal(size=(37, 8)).astype(np.float32)
W = rng.normal(size=(9, 8)).astype(np.float32)
Q = rng.normal(size=(6, 8)).astype(np.float32)
store = Store(store_dir)
live = store.open_index(
    ShardedVectorIndex.build_sharded(V, make_shard_mesh(1)))
live = live.add_documents(W).delete([2, 38, 40])

ref = {{e: live.search(Q, k=7, page=1000, engine=e)
       for e in ("postings", "codes", "onehot")}}

rec, _ = recover(store_dir, make_shard_mesh(4, 2))
assert rec.n_replicas == 2 and rec.n_appended == 9
for engine, (ri, rs) in ref.items():
    for merge in ("gather", "stream"):
        gi, gs = rec.search(Q, k=7, page=1000, engine=engine, merge=merge)
        assert np.array_equal(np.asarray(ri), np.asarray(gi)), (engine, merge)
        assert np.array_equal(np.asarray(rs), np.asarray(gs)), (engine, merge)

# and per-group: each replica column is a full, correct, addressable copy
for g in (0, 1):
    grp = rec.replica_group(g)
    gi, gs = grp.search(Q, k=7, page=1000, engine="codes")
    assert np.array_equal(np.asarray(ref["codes"][0]), np.asarray(gi)), g
    assert np.array_equal(np.asarray(ref["codes"][1]), np.asarray(gs)), g
store.close()
print("OK")
""")


def test_cluster_restore_group_on_4x2_mesh(tmp_path):
    """THE cluster acceptance pin: on the 4x2 mesh, a replica group is
    poisoned and marked down, the cluster keeps ingesting, and
    restore_group rebuilds the group FROM DISK onto its own device
    column -- after which it serves results bit-identical to the
    surviving group, including ops acked while it was down."""
    _run_subprocess(rf"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.cluster import ClusterEngine
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh
from repro.store import Store

rng = np.random.default_rng(2)
V = rng.normal(size=(41, 10)).astype(np.float32)
W = rng.normal(size=(7, 10)).astype(np.float32)
Q = rng.normal(size=(5, 10)).astype(np.float32)
sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(4, 2))
store = Store({str(tmp_path)!r})
cl = ClusterEngine(sidx, batch_size=4, k=5, page=1000, trim=None,
                   engine="codes", store=store)
try:
    cl.add_documents(W[:4])
    cl.inject_failure(1)
    cl.mark_down(1)
    cl.add_documents(W[4:])        # acked while group 1 is down
    cl.delete([3, 42])
    ref = [cl.search(q, stream="a", timeout=300) for q in Q]
    seq = cl.restore_group(1)
    assert seq == 3 and cl.health.is_up(1)
    got = [cl.search(q, stream="pin-elsewhere", timeout=300) for q in Q]
    for (ai, asc), (bi, bsc) in zip(ref, got):
        assert np.array_equal(ai, bi) and np.array_equal(asc, bsc)
finally:
    cl.close()
store.close()
print("OK")
""")
