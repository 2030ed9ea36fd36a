"""Every phase of a dispatch on the profiler's clock.

* the served search programs carry the phase scopes as metadata
  (``jax.named_scope``): ``df_lookup``, ``idf_psum``, ``phase1``,
  ``shard_rescore`` in the query phase, ``merge_select`` and ``rescore``
  in the merge;
* an engine whose tracer annotates opens, on its worker thread and in this
  order, ``repro.engine.wait``, ``repro.engine.batch_form``,
  ``repro.engine.dispatch`` (holding the index's ``repro.search.*`` spans,
  then ``repro.engine.readback``) and ``repro.engine.resolve``; without
  annotation none opens.
"""

import glob
import os
import threading

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.dist.shard_index as si
from repro.launch.mesh import make_shard_mesh
from repro.obs.tracing import Tracer, annotating, annotation
from repro.serve.engine import BatchedSearchEngine

N_DOCS, N_FEAT = 64, 16
QUERY_SCOPES = ("df_lookup", "idf_psum", "phase1", "shard_rescore")


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(13)
    return (rng.normal(size=(N_DOCS, N_FEAT)).astype(np.float32),
            rng.normal(size=(8, N_FEAT)).astype(np.float32))


def _lowered_texts(monkeypatch, index, queries, engine):
    """The lowered text, with its locations, of each search program the
    search called, by program name."""
    texts = {}
    for name in ("_query_phase", "_merge_select", "_merge_select_seg",
                 "_rescore"):
        real = getattr(si, name)

        def spy(*a, _real=real, _name=name, **k):
            texts[_name] = _real.lower(*a, **k).as_text(debug_info=True)
            return _real(*a, **k)

        monkeypatch.setattr(si, name, spy)
    index.search(queries, k=5, page=32, engine=engine)
    return texts


@pytest.mark.parametrize("engine", ["fused", "codes"])
def test_query_phase_and_merge_carry_the_scopes(monkeypatch, corpus, engine):
    x, q = corpus
    idx = si.ShardedVectorIndex.build_sharded(x, make_shard_mesh(1))
    texts = _lowered_texts(monkeypatch, idx, q, engine)
    for scope in QUERY_SCOPES:
        assert f"/{scope}/" in texts["_query_phase"], scope
    assert "/merge_select/" in texts["_merge_select"]
    assert "/rescore/" in texts["_rescore"]


def test_segmented_merge_carries_its_scope(monkeypatch, corpus):
    x, q = corpus
    idx = si.ShardedVectorIndex.build_sharded(x[:48], make_shard_mesh(1))
    idx = idx.add_documents(x[48:])
    texts = _lowered_texts(monkeypatch, idx, q, "fused")
    assert "_merge_select" not in texts
    assert "/merge_select/" in texts["_merge_select_seg"]
    for scope in QUERY_SCOPES:
        assert f"/{scope}/" in texts["_query_phase"], scope


def _worker_spans(trace_dir):
    """[(name, start_ns, end_ns)] of the ``repro.*`` spans on the one host
    thread that opened a dispatch, in start order."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    lines = []
    for line in host.lines:
        spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in line.events if e.name.startswith("repro.")]
        if any(n == "repro.engine.dispatch" for n, _, _ in spans):
            lines.append(sorted(spans, key=lambda s: (s[1], -s[2])))
    assert len(lines) <= 1, "dispatch spans on more than one thread"
    return lines[0] if lines else []


def _serve_traced(tmp_path, corpus, tracer):
    x, q = corpus
    idx = si.ShardedVectorIndex.build_sharded(x, make_shard_mesh(1))
    with jax.profiler.trace(str(tmp_path)):
        # batches form only when full: two of 4, whatever the timing
        eng = BatchedSearchEngine(idx, batch_size=4, max_wait_s=60.0, k=5,
                                  page=32, trim=None, engine="fused",
                                  tracer=tracer)
        try:
            for f in [eng.submit(v) for v in q]:
                f.result(timeout=120)
        finally:
            eng.close()
    return _worker_spans(str(tmp_path))


def test_engine_phases_in_order_on_the_worker_thread(tmp_path, corpus):
    """On the worker thread each batch's phases run in order, and the
    second batch is dispatched before the first is read back: the two
    dispatch spans overlap, each holding its own search phases and ending
    with its own readback."""
    spans = _serve_traced(tmp_path, corpus, Tracer(annotate=True))
    names = [n for n, _, _ in spans]
    search = ["repro.search.encode", "repro.search.query_phase",
              "repro.search.merge"]
    # 8 queries, batches of 4: two dispatches, then the wait close ends
    assert names == (
        ["repro.engine.wait", "repro.engine.batch_form",
         "repro.engine.dispatch"] + search
        + ["repro.engine.wait", "repro.engine.batch_form",
           "repro.engine.dispatch"] + search
        + ["repro.engine.readback", "repro.engine.resolve",
           "repro.engine.wait", "repro.engine.readback",
           "repro.engine.resolve", "repro.engine.wait"]), names
    d1, d2 = [(s, e) for n, s, e in spans if n == "repro.engine.dispatch"]
    assert d2[0] < d1[1]                        # two batches in flight
    # dispatch b opens before its encode and closes with its readback,
    # before its resolve
    encodes = [s for n, s, _ in spans if n == "repro.search.encode"]
    readbacks = [e for n, _, e in spans if n == "repro.engine.readback"]
    resolves = [s for n, s, _ in spans if n == "repro.engine.resolve"]
    for (s0, e0), enc, rb, res in zip((d1, d2), encodes, readbacks,
                                      resolves):
        assert s0 <= enc and rb <= e0 <= res
    # the engine's own spans never overlap one another but for dispatch
    own = [(s, e) for n, s, e in spans if n.startswith("repro.engine.")
           and n not in ("repro.engine.dispatch", "repro.engine.readback")]
    for (_, e), (s, _) in zip(own, own[1:]):
        assert s >= e


def test_no_span_opens_without_annotation(tmp_path, corpus):
    assert _serve_traced(tmp_path, corpus, None) == []
    assert _serve_traced(tmp_path / "sampled", corpus,
                         Tracer(annotate=False)) == []


def test_annotating_holds_for_its_own_thread_only():
    seen = {}

    def other():
        seen["other"] = type(annotation("x")).__name__

    with annotating():
        seen["here"] = type(annotation("x")).__name__
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    assert seen == {"here": "TraceAnnotation", "other": "nullcontext"}
    assert type(annotation("x")).__name__ == "nullcontext"
