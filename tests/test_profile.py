"""Observability v2 (repro/obs): _profile trees, slow log, compile
watch, exporter.

The pinned invariants:

* **bit-parity with everything ON** -- results served with metrics +
  tracing + slow log + compile watch + ``profile=True`` are
  bit-identical to a bare engine, for every engine including the fused
  kernels, on an index with appended segments and tombstones (all
  instrumentation is host-side; ``block_until_ready`` fences change
  when values are observed, never the values);
* **profile trees reconcile** -- a request's ``queue_wait`` +
  ``batch_form`` + ``dispatch`` children tile its root total exactly
  (shared clock reads; float addition error only), and the dispatch
  subtree names the kernel path taken;
* **tail capture beats head sampling** -- with a 1/16-sampled tracer,
  every slow or failed request is still captured by the slow log, with
  a promoted profile view; the ring stays bounded and the JSONL sink
  gets every capture;
* **recompiles are observable** -- compiles count per (region,
  signature), a repeat shape hits the jit cache silently, and after
  ``mark_steady()`` any attributed compile is a hard :meth:`check`
  failure while unattributed host compiles stay exempt;
* the Prometheus exposition and the snapshot-history exporter render
  exactly what the registry holds.
"""

import json
import time

import numpy as np
import pytest

from repro.cluster import ClusterEngine
from repro.core import VectorIndex
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh
from repro.obs import (CompileWatch, MetricsExporter, MetricsRegistry,
                       ProfileNode, SlowLog, Tracer, format_profile_tree,
                       prometheus_text)
from repro.serve.engine import BatchedSearchEngine

N_DOCS, N_FEAT = 60, 16

ALL_ENGINES = ("codes", "postings", "onehot", "fused", "fused_int8")


@pytest.fixture(scope="module")
def sidx():
    """Sharded index with an appended generation and tombstones: the
    profile tree's per-generation children and the parity pins must
    hold on the full segment lifecycle, not just a fresh build."""
    rng = np.random.default_rng(0)
    idx = ShardedVectorIndex.build_sharded(
        rng.normal(size=(N_DOCS, N_FEAT)).astype(np.float32),
        make_shard_mesh(1), seal_threshold=16)
    idx = idx.add_documents(
        rng.normal(size=(24, N_FEAT)).astype(np.float32))
    return idx.delete(np.array([3, N_DOCS + 2]))


@pytest.fixture()
def queries():
    return np.random.default_rng(1).normal(
        size=(6, N_FEAT)).astype(np.float32)


def _full_obs_engine(index, engine, reg=None, batch_size=4, k=5, **kw):
    reg = reg if reg is not None else MetricsRegistry()
    return BatchedSearchEngine(
        index, batch_size=batch_size, k=k, page=N_DOCS, trim=None,
        engine=engine, metrics=reg, tracer=Tracer(sample=1.0 / 16),
        slowlog=SlowLog(threshold_s=0.0, metrics=reg),
        compile_watch=CompileWatch(metrics=reg), **kw)


# ------------------------------------------------------------ profile trees
def test_vector_index_profile_children_and_parity(queries):
    idx = VectorIndex.build(np.random.default_rng(2).normal(
        size=(N_DOCS, N_FEAT)).astype(np.float32))
    for engine in ALL_ENGINES:
        prof = ProfileNode("q")
        ids, scores = idx.search(queries, k=5, page=N_DOCS,
                                 engine=engine, profile=prof)
        bare_ids, bare_scores = idx.search(queries, k=5, page=N_DOCS,
                                           engine=engine)
        assert np.array_equal(np.asarray(ids), np.asarray(bare_ids))
        assert np.array_equal(np.asarray(scores), np.asarray(bare_scores))
        names = [c.name for c in prof.children]
        assert names == ["encode", "phase1", "rescore"], engine
        phase1 = prof.children[1]
        want_kernel = engine if engine in ("fused", "fused_int8") \
            else "composed"
        assert phase1.attrs["kernel"] == want_kernel
        assert phase1.attrs["candidates"] > 0
        assert all(c.duration_s >= 0.0 for c in prof.children)


def test_engine_profile_tree_reconciles(sidx, queries):
    reg = MetricsRegistry()
    eng = _full_obs_engine(sidx, "codes", reg=reg)
    try:
        ids, scores, tree = eng.search(queries[0], timeout=60,
                                       profile=True)
        bare_ids, bare_scores = eng.search(queries[0], timeout=60)
        assert np.array_equal(ids, bare_ids)
        assert np.array_equal(scores, bare_scores)
        assert tree["name"] == "query"
        kids = {c["name"]: c for c in tree["children"]}
        assert list(kids) == ["queue_wait", "batch_form", "dispatch"]
        # shared clock reads: the three phases tile the total EXACTLY
        # (float addition error only)
        tiled = sum(c["duration_s"] for c in kids.values())
        assert abs(tree["duration_s"] - tiled) < 1e-9
        disp = kids["dispatch"]
        assert disp["attrs"]["engine"] == "codes"
        disp_kids = {c["name"]: c for c in disp["children"]}
        assert {"encode", "phase1", "merge_select",
                "rescore"} <= set(disp_kids)
        phase1 = disp_kids["phase1"]
        assert phase1["attrs"]["kernel"] == "composed"
        # per-generation candidate children: base + the sealed/active
        # generations, candidate counts summing to the phase total
        gen_kids = {c["name"]: c for c in phase1["children"]}
        assert "base" in gen_kids
        assert sum(c["attrs"]["candidates"]
                   for n, c in gen_kids.items()
                   if not n.startswith("group")) \
            == phase1["attrs"]["candidates"]
        # the rendering names every phase
        text = format_profile_tree(tree)
        for name in ("query", "queue_wait", "dispatch", "phase1",
                     "rescore"):
            assert name in text
        # dispatch duration is the same observation the latency
        # histogram recorded (one request per batch here)
        assert reg.histogram("engine.dispatch.latency_s").count >= 1
    finally:
        eng.close()


def test_full_instrumentation_bit_parity_all_engines(sidx, queries):
    """THE acceptance pin: every engine, segments + tombstones live,
    metrics + tracer + slow log + compile watch + profile trees ON --
    and the v3 plane polled between requests (device byte accounting +
    node stats) -- results bit-identical to a bare engine."""
    from repro.obs import device_bytes, node_stats

    for engine in ALL_ENGINES:
        bare = BatchedSearchEngine(
            sidx, batch_size=4, k=5, page=N_DOCS, trim=None,
            engine=engine, metrics=MetricsRegistry(enabled=False))
        inst = _full_obs_engine(sidx, engine)
        try:
            for q in queries:
                bi, bs = bare.search(q, timeout=60)
                ii, iscore, tree = inst.search(q, timeout=60,
                                               profile=True)
                # poll the telemetry plane mid-serve, exactly like the
                # smoke-health poller thread does
                dev = device_bytes(sidx, reconcile=False)
                assert dev["total_bytes"] > 0
                node_stats(inst)
                assert np.array_equal(bi, ii), engine
                assert np.array_equal(bs, iscore), engine
                assert tree["children"], engine
        finally:
            bare.close()
            inst.close()


def test_cluster_profile_routing_and_counters(sidx, queries):
    reg = MetricsRegistry()
    cl = ClusterEngine([sidx, sidx], batch_size=4, k=5, page=N_DOCS,
                       trim=None, engine="codes", metrics=reg)
    try:
        ids, scores, tree = cl.profile(queries[0], stream="s")
        ref = cl.search(queries[0], stream="s", timeout=60)
        assert np.array_equal(ids, ref[0])
        assert np.array_equal(scores, ref[1])
        assert tree["name"] == "cluster.query"
        assert tree["attrs"]["n_groups"] == 2
        route, query = tree["children"]
        assert route["name"] == "route"
        assert route["attrs"]["up_groups"] == 2
        assert query["name"] == "query"
        assert query["attrs"]["group"] == route["attrs"]["group"]
        # profiled requests ride the same counters as plain ones
        assert reg.value("cluster.requests.submitted") == 2
        assert reg.value("cluster.requests.completed") == 2
        g = route["attrs"]["group"]
        assert reg.value("cluster.requests.group_completed", group=g) == 2
    finally:
        cl.close()


# ----------------------------------------------------------------- slow log
def test_slowlog_tail_capture_beats_head_sampling(sidx, queries):
    """With a 1/16 tracer, 6 slow requests leave at most one sampled
    trace -- but the slow log captures ALL of them, each promoted to a
    profile view."""
    reg = MetricsRegistry()
    tr = Tracer(sample=1.0 / 16)
    slog = SlowLog(threshold_s=0.0, metrics=reg)   # everything is "slow"
    eng = BatchedSearchEngine(sidx, batch_size=4, k=5, page=N_DOCS,
                              trim=None, engine="codes", metrics=reg,
                              tracer=tr, slowlog=slog)
    try:
        for q in queries:
            eng.search(q, timeout=60)
    finally:
        eng.close()
    assert tr.stats()["sampled"] == 1              # head sampling dropped 5
    st = slog.stats()
    assert st["seen"] == len(queries)
    assert st["captured"] == len(queries)          # tail capture got all 6
    for rec in slog.dump():
        assert rec["slowlog"]["reason"] == "slow"
        assert rec["slowlog"]["duration_s"] >= 0.0
        prof = rec["profile"]
        assert {"queue_wait", "batch_form", "dispatch"} <= {
            c["name"] for c in prof["children"]}
    assert reg.value("slowlog.captured") == len(queries)


def test_slowlog_captures_errors_below_threshold(sidx, queries):
    """A failed request is captured even when it was fast (and head
    sampling would have dropped it)."""
    slog = SlowLog(threshold_s=10.0)               # nothing is "slow"
    eng = BatchedSearchEngine(sidx, batch_size=2, k=5, page=N_DOCS,
                              trim=None, engine="codes",
                              metrics=MetricsRegistry(),
                              tracer=Tracer(sample=1.0 / 16), slowlog=slog)
    try:
        eng.search(queries[0], timeout=60)         # fast + healthy: dropped
        with pytest.raises(Exception):
            eng.search(np.ones(N_FEAT + 3, np.float32), timeout=60)
    finally:
        eng.close()
    st = slog.stats()
    assert st["seen"] == 2
    assert st["captured"] == st["errors"] == 1
    (rec,) = slog.dump()
    assert rec["slowlog"]["reason"] == "error"
    assert "error" in rec["attrs"]


def test_slowlog_ring_bound_and_jsonl_sink(tmp_path):
    path = tmp_path / "slow.jsonl"
    slog = SlowLog(threshold_s=0.0, capacity=4, path=str(path))
    for i in range(7):
        t = slog.start("query", n=i)
        t.span("work").end()
        t.finish()
    st = slog.stats()
    assert st["seen"] == st["captured"] == 7
    assert st["retained"] == 4                     # ring keeps the newest
    assert [r["attrs"]["n"] for r in slog.dump()] == [3, 4, 5, 6]
    slog.close()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 7                         # the sink keeps ALL
    assert all("profile" in l and "slowlog" in l for l in lines)
    assert slog.dump(clear=True) and slog.dump() == []
    with pytest.raises(ValueError, match="threshold"):
        SlowLog(threshold_s=-1.0)
    with pytest.raises(ValueError, match="capacity"):
        SlowLog(capacity=0)


def test_slowlog_threshold_filters_fast_requests():
    slog = SlowLog(threshold_s=10.0, metrics=MetricsRegistry())
    t = slog.start("query")
    t.finish()                                     # fast, healthy: dropped
    st = slog.stats()
    assert st["seen"] == 1 and st["captured"] == 0
    t = slog.start("query")
    t.finish(error="boom")                         # errors always kept
    st = slog.stats()
    assert st["captured"] == st["errors"] == 1 and st["slow"] == 0


# ------------------------------------------------------------ compile watch
def test_compile_watch_counts_shapes_and_steady_state():
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    w = CompileWatch(metrics=reg)
    f = jax.jit(lambda x: x * 2 + 1)
    # inputs built OUTSIDE any region: their own fill compiles must not
    # be attributed to "fn"
    x3, x4, x5 = jnp.ones((3,)), jnp.ones((4,)), jnp.ones((5,))
    with w.region("fn", sig=((3,),)):
        f(x3)
    base = w.compiles_total
    assert base >= 1
    with w.region("fn", sig=((3,),)):
        f(x3)                                      # jit cache hit: silent
    assert w.compiles_total == base
    with w.region("fn", sig=((4,),)):
        f(x4)                                      # new abstract shape
    assert w.compiles_total == base + 1
    st = w.stats()
    assert st["by_function"] == {"fn": base + 1}
    assert st["signatures"] == 2 and not st["steady"]
    assert reg.value("compile.total", fn="fn") == base + 1
    assert reg.histogram("compile.duration_s", fn="fn").count == base + 1

    w.mark_steady()
    w.check()                                      # clean: no-op
    assert w.compiles_steady_state == 0
    with w.region("fn", sig=((5,),)):
        f(x5)                                      # steady-state recompile
    assert w.compiles_steady_state == 1
    (ev,) = w.stats()["steady_events"]
    assert ev["fn"] == "fn" and not ev["repeat_sig"]
    with pytest.raises(RuntimeError, match="steady-state recompile"):
        w.check()
    w.reset()
    assert w.compiles_total == 0 and not w.stats()["steady"]


def test_compile_watch_unattributed_never_steady():
    """Host-side compiles outside any region must not trip the
    steady-state guard of a serving watch."""
    import jax
    import jax.numpy as jnp

    w = CompileWatch(metrics=MetricsRegistry())
    w.mark_steady()
    jax.jit(lambda x: x - 7)(jnp.ones((3,)))       # no region on this thread
    assert w.compiles_steady_state == 0
    w.check()                                      # still clean


def test_engine_dispatch_attributed_and_steady_after_warmup(sidx, queries):
    """The engine's serving path compiles land in the injected watch,
    and a warmed engine re-serving the same shapes stays steady."""
    reg = MetricsRegistry()
    w = CompileWatch(metrics=reg)
    # batch_size/k unique to this test: the jit cache is process-wide,
    # so a shape another test already compiled would record nothing here
    eng = BatchedSearchEngine(sidx, batch_size=3, k=7, page=N_DOCS,
                              trim=None, engine="codes", metrics=reg,
                              compile_watch=w)
    try:
        eng.search(queries[0], timeout=60)         # warmup
        assert w.compiles_total >= 1
        fns = set(w.stats()["by_function"])
        assert any(f.startswith(("engine.", "search.")) for f in fns)
        w.mark_steady()
        for q in queries:
            eng.search(q, timeout=60)
        assert w.compiles_steady_state == 0
        w.check()
    finally:
        eng.close()


# --------------------------------------------------------------- exporters
def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("engine.requests.completed", group=0).inc(5)
    reg.gauge("engine.queue.depth").set(3.0)
    h = reg.histogram("engine.queue.wait_s")
    h.observe_many([0.001, 0.002, 0.004])
    text = prometheus_text(reg.snapshot())
    lines = text.splitlines()
    assert "# TYPE repro_engine_requests_completed_total counter" in lines
    assert 'repro_engine_requests_completed_total{group="0"} 5' in lines
    assert "repro_engine_queue_depth 3.0" in lines
    assert "repro_engine_queue_wait_s_count 3" in lines
    for q in ("0.50", "0.90", "0.99", "0.999"):
        assert any(f'quantile="{q}"' in l for l in lines), q
    # sum line carries the exact histogram sum
    (sum_line,) = [l for l in lines
                   if l.startswith("repro_engine_queue_wait_s_sum")]
    assert float(sum_line.split()[-1]) == pytest.approx(0.007)


def test_metrics_exporter_history_and_jsonl(tmp_path):
    path = tmp_path / "metrics.jsonl"
    reg = MetricsRegistry()
    c = reg.counter("t.ticks")
    exp = MetricsExporter(reg, path=str(path), capacity=3)
    for i in range(5):
        c.inc()
        exp.collect()
    hist = exp.history()
    assert len(hist) == 3                          # bounded ring
    ts = [h["t_monotonic"] for h in hist]
    assert ts == sorted(ts)                        # monotonic timestamps
    assert [h["metrics"]["counters"]["t.ticks"][""] for h in hist] \
        == [3, 4, 5]
    exp.stop()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 5                         # the sink keeps ALL
    assert lines[0]["metrics"]["counters"]["t.ticks"][""] == 1
    assert "repro_t_ticks_total 5" in exp.text()


def test_metrics_exporter_background_thread():
    reg = MetricsRegistry()
    exp = MetricsExporter(reg, interval_s=0.01)
    exp.start()
    deadline = time.monotonic() + 5.0
    while not exp.history() and time.monotonic() < deadline:
        time.sleep(0.005)
    exp.stop()
    assert exp.history()                           # collected on its own
    n = len(exp.history())
    time.sleep(0.05)
    assert len(exp.history()) == n                 # stopped means stopped


# --------------------------------------------------- stats-layer integration
def test_engine_stats_carry_slowlog_and_compile_sections(sidx, queries):
    # k=6 keeps this dispatch shape un-cached by earlier tests, so the
    # compile section is guaranteed non-empty
    eng = _full_obs_engine(sidx, "fused", k=6)
    try:
        for q in queries[:3]:
            eng.search(q, timeout=60)
        st = eng.stats()
        assert st["slowlog"]["seen"] == 3
        assert st["slowlog"]["captured"] == 3
        assert st["compile"]["compiles_total"] >= 1
        assert "steady_events" not in st["compile"]   # stats stay compact
        assert st["kernel_path"] == {"fused": 3}
        assert "p999" in st["dispatch_latency_s"]
    finally:
        eng.close()


def test_cluster_stats_carry_slowlog_and_compile_sections(sidx, queries):
    reg = MetricsRegistry()
    # k=4 keeps the dispatch shape un-cached (see the engine stats test)
    cl = ClusterEngine([sidx, sidx], batch_size=4, k=4, page=N_DOCS,
                       trim=None, engine="codes", metrics=reg,
                       slowlog=SlowLog(threshold_s=0.0, metrics=reg),
                       compile_watch=CompileWatch(metrics=reg))
    try:
        for i, q in enumerate(queries):
            cl.search(q, stream=i % 2, timeout=60)
        st = cl.stats()
        assert st["slowlog"]["seen"] == len(queries)
        assert st["slowlog"]["captured"] == len(queries)
        assert st["compile"]["compiles_total"] >= 1
        assert st["compile"]["compiles_steady_state"] == 0
    finally:
        cl.close()
