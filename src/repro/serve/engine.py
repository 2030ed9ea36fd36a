"""Batched request serving for the vector-search index.

A real deployment fronts the TPU program with a request batcher: incoming
query vectors are buffered until ``max_batch`` or ``max_wait_s`` (whichever
first), padded to the compiled batch shape, executed as ONE jitted search,
and scattered back to their futures.  The worker dispatches the next
batch as soon as it forms and only then reads the previous one back, so
up to two batches are on the device and the device does not wait on the
host between them.  This mirrors the paper's observation
(Table 3) that parallel querying trades per-request latency for throughput --
here the trade is explicit: batch 1 = lowest latency, batch N = N-fold
throughput at ~constant step time (the TPU is batch-insensitive until the
code-match stream saturates HBM).

The engine is index-polymorphic: anything with the ``VectorIndex.search``
contract serves, in particular :class:`repro.dist.shard_index.
ShardedVectorIndex` -- one batcher then fronts a whole doc-sharded mesh
(the ES coordinating-node arrangement), and the per-request results are
bit-identical to the single-device index for ``page >= n_docs``.

Fronting a sharded index, each submitted batch runs the ES query/fetch
protocol end to end: per-shard phase-1 + local top-k under ``shard_map``,
then the coordinating merge.  ``merge="stream"`` makes that merge
asynchronous on-device -- per-shard candidate pages ring-rotate along the
``data`` axis and stream into the coordinator's running top-k, so the
communication of one shard's page overlaps the fold of the previous one
instead of a single blocking all-gather.  On a ``(data, replica)`` mesh
(``make_shard_mesh(shards, replicas)``) the batch itself round-robins
across replica groups, each holding a full copy of the corpus: R groups
answer Q/R queries apiece, multiplying QPS without touching quality.

Lifecycle: ``submit`` after ``close`` raises ``RuntimeError`` (the queue
has no worker to drain it); a search that raises inside the worker fails
only that batch's futures (``set_exception``) and the worker keeps
serving subsequent batches; ``close`` drains everything already queued
before returning.

**Hot ingest**: ``add_documents`` grows a sharded index ES-style (append
segments, :meth:`repro.dist.shard_index.ShardedVectorIndex.add_documents`)
and atomically swaps the new index in under the engine lock -- the batch
in flight finishes against the old index, every batch dequeued afterwards
sees the new documents.  ``delete`` tombstones the same way.  Ingest is a
control-plane operation: submits block for its (short) duration, which is
the ES refresh semantics.

**Hot swap**: ``swap_index(new, expected=old)`` is the compare-and-swap
the background maintenance daemon (:mod:`repro.cluster.maintenance`)
compacts through: the rebuild runs OUTSIDE the lock against a snapshot,
the swap takes the lock only for the pointer flip, and a concurrent
``add_documents``/``delete`` (which changes ``self.index``) makes the CAS
return False so the daemon retries against the fresh snapshot -- no
in-flight query is ever dropped and no ingest is ever lost.  The CAS also
carries the durability commit metadata: a
:class:`repro.store.durable.DurableIndex` rides through the swap with its
``translog_seq`` intact, so whoever wins the CAS hands the daemon a
consistent (state, translog position) pair to roll a commit point from.

``pending`` (queued + in-flight request count) is the router's load
signal for least-loaded spill across replica-group batchers
(:mod:`repro.cluster.router`).

**Observability** (:mod:`repro.obs`): the batcher records request
counters, batch occupancy, measured queue wait, and dispatch latency
into a :class:`~repro.obs.metrics.MetricsRegistry` (labelled ``group=g``
when fronting one replica group), and appends per-request spans --
queue wait, batch formation, device dispatch -- to any
:class:`~repro.obs.tracing.Trace` riding the submit.  All timestamps
are host-side, taken around the jitted program dispatch; the batch
deadline and the queue-wait spans share ONE clock read per dequeue, so
the batcher's accounting and the trace always agree on a wait.  With a
``Tracer(annotate=True)`` every phase of the worker's loop (wait, batch
formation, dispatch and its readback, resolve) is also a profiler span.
``stats()`` is the ES ``_cat/thread_pool`` view of this batcher.
"""

from __future__ import annotations

import inspect
import threading
import time
from concurrent.futures import Future
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import TrimFilter, VectorIndex
from repro.obs.compile_watch import active_watch
from repro.obs.metrics import default_registry
from repro.obs.profile import ProfileNode
from repro.obs.slowlog import start_request_trace
from repro.obs.tracing import annotating, annotation

__all__ = ["BatchedSearchEngine"]


# how often a worker waiting for the next batch looks whether the batch
# it holds on the device is done (it reads that one back once it is)
_HELD_POLL_S = 0.0005


class _InFlight:
    """One dispatched batch, from its dispatch to its readback."""

    __slots__ = ("batch", "index", "t_deq", "t_dispatch", "ids", "scores",
                 "prof", "error", "span")

    def __init__(self, batch, index, t_deq):
        self.batch, self.index, self.t_deq = batch, index, t_deq
        self.t_dispatch = t_deq     # overwritten once the batch is built
        self.ids = self.scores = self.prof = self.error = self.span = None

    def ready(self) -> bool:
        """Its answers can be read back without waiting on the device."""
        return self.error is not None or all(
            getattr(a, "is_ready", lambda: True)()
            for a in (self.ids, self.scores))


def _accepts_profile(index) -> bool:
    """Whether ``index.search`` takes the ``profile`` kwarg (the engine
    is index-polymorphic; test doubles and plain callables may not).
    ``**kwargs`` wrappers count -- they forward to an index that does."""
    try:
        params = inspect.signature(index.search).parameters
    except (TypeError, ValueError):  # pragma: no cover - builtin search
        return False
    return "profile" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


class BatchedSearchEngine:
    def __init__(
        self,
        index: "VectorIndex | ShardedVectorIndex",  # noqa: F821 - any .search
        batch_size: int = 32,
        max_wait_s: float = 0.005,
        k: int = 10,
        page: int = 320,
        trim: Optional[TrimFilter] = TrimFilter(0.05),
        engine: str = "codes",
        merge: Optional[str] = None,
        max_postings: "Optional[int | str]" = None,
        metrics=None,
        tracer=None,
        group: Optional[int] = None,
        donate_ingest: bool = False,
        slowlog=None,
        compile_watch=None,
    ):
        self.index = index
        self.batch_size = batch_size
        self.max_wait_s = max_wait_s
        self.k, self.page, self.trim, self.engine = k, page, trim, engine
        # merge transport for sharded indexes ("gather" | "stream") and the
        # postings window ("auto" = size from the shard code distribution);
        # None omits the kwarg so plain VectorIndex keeps serving unchanged
        self.merge = merge
        self.max_postings = max_postings
        # opt-in buffer donation for hot ingest: add_documents may donate
        # the active append buffers to the update program -- but ONLY when
        # the current index is not the snapshot a batch is searching right
        # now (the worker records its snapshot in _serving under the lock;
        # donating a buffer a dispatched program still reads would be a
        # use-after-free)
        self.donate_ingest = donate_ingest
        self._serving: list = []
        # observability: metrics series carry the replica-group label when
        # this batcher fronts one group of a cluster; instruments are
        # cached here so the worker pays one lock-op per record, not a
        # registry lookup
        self.metrics = metrics if metrics is not None else default_registry()
        self.tracer = tracer
        # tail-based slow-query capture (repro.obs.slowlog): with one
        # attached, EVERY request carries a span skeleton and the slow
        # log's threshold decides retention at finish -- independent of
        # the tracer's head sampling
        self.slowlog = slowlog
        # recompile telemetry (repro.obs.compile_watch): the dispatch,
        # ingest, and delete seams run inside watch regions so any XLA
        # compile they trigger is attributed and counted
        self.compile_watch = (compile_watch if compile_watch is not None
                              else active_watch())
        self.group = group
        self._metric_labels = {} if group is None else {"group": group}
        lb = self._metric_labels
        self._c_submitted = self.metrics.counter(
            "engine.requests.submitted", **lb)
        self._c_completed = self.metrics.counter(
            "engine.requests.completed", **lb)
        self._c_failed = self.metrics.counter("engine.requests.failed", **lb)
        self._h_occupancy = self.metrics.histogram(
            "engine.batch.occupancy", **lb)
        self._h_wait = self.metrics.histogram("engine.queue.wait_s", **lb)
        self._h_dispatch = self.metrics.histogram(
            "engine.dispatch.latency_s", **lb)
        # which phase-1 path served each batch -- the fused-kernel rollout
        # counter (label = engine name, so a fleet-wide registry shows the
        # fused/composed mix at a glance)
        self._c_kernel_path = self.metrics.counter(
            "engine.kernel_path", engine=self.engine, **lb)
        self._lock = threading.Condition()
        # queue items: (query, future, enqueue timestamp, trace,
        # want_profile)
        self._queue: List[tuple] = []
        self._stop = False
        self._inflight = 0
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    # ------------------------------------------------------------------ API
    def submit(self, query_vec: np.ndarray, trace=None,
               profile: bool = False) -> Future:
        """Queue one query -> Future of (ids, scores).  ``trace`` is an
        optional :class:`~repro.obs.Trace` the worker appends its spans
        to (the cluster router passes one down); without it, an engine
        constructed with a ``tracer``/``slowlog`` admits its own (head
        sampling for the tracer, a retained-on-slow skeleton for the
        slow log).  With ``profile=True`` the future resolves to
        ``(ids, scores, profile_dict)`` -- the per-phase execution tree
        (:mod:`repro.obs.profile`)."""
        fut: Future = Future()
        if trace is None:
            trace = start_request_trace(self.tracer, self.slowlog, "query")
            if trace:
                t = trace
                fut.add_done_callback(
                    lambda f: t.finish(
                        error=None if f.cancelled() or f.exception()
                        is None else repr(f.exception())))
        with self._lock:
            if self._stop:
                raise RuntimeError("engine closed")
            self._queue.append((np.asarray(query_vec, np.float32), fut,
                                time.monotonic(), trace, profile))
            self._lock.notify()
        self._c_submitted.inc()
        return fut

    def search(self, query_vec: np.ndarray, timeout: float = 10.0,
               profile: bool = False):
        return self.submit(query_vec, profile=profile).result(
            timeout=timeout)

    @property
    def pending(self) -> int:
        """Queued + in-flight request count -- the cluster router's load
        signal for stream-affinity spill decisions."""
        with self._lock:
            return len(self._queue) + self._inflight

    def add_documents(self, vectors: np.ndarray) -> int:
        """Hot-add documents; returns the first global id assigned.

        The grown index (per-shard append segments) replaces ``self.index``
        atomically: in-flight batches finish on the old index, subsequent
        batches search the new docs.  Raises ``RuntimeError`` after
        ``close`` and ``TypeError`` for indexes without incremental ingest
        (plain :class:`VectorIndex` is immutable -- shard it first).

        With ``donate_ingest=True`` the update donates the old append
        buffers to the update program (zero steady-state allocations) --
        guarded by the serving snapshot: if the batch in flight is
        searching the CURRENT index, its buffers are still being read and
        donation is skipped for this call.
        """
        with self._lock:
            if self._stop:
                raise RuntimeError("engine closed")
            add = getattr(self.index, "add_documents", None)
            if add is None:
                raise TypeError(
                    f"{type(self.index).__name__} does not support "
                    "incremental ingest; serve a ShardedVectorIndex")
            first_id = self.index.n_ids
            # donation is safe only when nothing else holds this index:
            # the engine owns the only reference unless the in-flight
            # batch snapshotted exactly this object
            donate = (self.donate_ingest
                      and not any(s is self.index for s in self._serving)
                      and "donate" in inspect.signature(add).parameters)
            t0 = time.monotonic()
            with self.compile_watch.region(
                    "engine.ingest", sig=(np.asarray(vectors).shape,)):
                self.index = (add(vectors, donate=True) if donate
                              else add(vectors))
            latency = time.monotonic() - t0
        # ingest apply latency measured inside the lock -- this is the
        # stall submits see, the number the segment story exists to bound
        # (seals amortise; no per-op full rebuild)
        self.metrics.histogram("engine.ingest.latency_s",
                               **self._metric_labels).observe(latency)
        self.metrics.counter("engine.ingest.added_docs",
                             **self._metric_labels).inc(
            int(np.asarray(vectors).shape[0]))
        return first_id

    def delete(self, ids) -> None:
        """Hot-tombstone documents by global id: the pruned index swaps in
        under the engine lock (same semantics as :meth:`add_documents` --
        in-flight batches finish on the old index, later batches never see
        the dead docs).  Feeds ``index.tombstone_ratio``, the maintenance
        daemon's auto-compaction trigger."""
        with self._lock:
            if self._stop:
                raise RuntimeError("engine closed")
            delete = getattr(self.index, "delete", None)
            if delete is None:
                raise TypeError(
                    f"{type(self.index).__name__} does not support "
                    "deletes; serve a ShardedVectorIndex")
            t0 = time.monotonic()
            with self.compile_watch.region(
                    "engine.delete", sig=(len(np.atleast_1d(ids)),)):
                self.index = delete(ids)
            latency = time.monotonic() - t0
        self.metrics.histogram("engine.ingest.latency_s",
                               **self._metric_labels).observe(latency)
        self.metrics.counter("engine.ingest.delete_ops",
                             **self._metric_labels).inc()

    def swap_index(self, new_index, expected=None) -> bool:
        """Atomically replace the served index (hot swap, no queries
        dropped).  With ``expected`` this is a compare-and-swap: the flip
        happens only while ``self.index is expected``, so a maintenance
        rebuild computed from a snapshot can never clobber a concurrent
        ingest -- it returns False and the caller retries on fresh state.
        """
        with self._lock:
            if self._stop:
                raise RuntimeError("engine closed")
            if expected is not None and self.index is not expected:
                return False
            self.index = new_index
        self.metrics.counter("engine.swaps", **self._metric_labels).inc()
        return True

    def stats(self) -> dict:
        """ES ``_cat/thread_pool``-style snapshot of this batcher: queue
        depth, in-flight count, request counters, occupancy + queue-wait
        + dispatch-latency histograms, and the served index's doc/segment
        stats (see :func:`repro.obs.stats.engine_stats`)."""
        from repro.obs.stats import engine_stats

        return engine_stats(self)

    def node_stats(self) -> dict:
        """ES ``GET _nodes/stats``: per-device residency of the served
        index (see :func:`repro.obs.stats.node_stats`)."""
        from repro.obs.stats import node_stats

        return node_stats(self)

    def device_stats(self) -> dict:
        """Exact index-resident byte accounting for the served index --
        per leaf, per section, per device, reconciled against
        ``jax.live_arrays()`` (see :func:`repro.obs.device.
        device_bytes`)."""
        from repro.obs.device import device_bytes

        return device_bytes(self.index)

    def close(self):
        with self._lock:
            self._stop = True
            self._lock.notify()
        self._worker.join()

    # --------------------------------------------------------------- worker
    def _run(self):
        # with an annotating tracer every phase of the worker's loop (and
        # the index's search phases under it) opens a profiler span, each
        # at the clock read its metrics and trace spans use.
        # Two batches may be on the device at once: batch n+1 is
        # dispatched as soon as it forms, and only then is batch n read
        # back and resolved, so the device runs n+1 through the host's
        # readback, resolve and next dispatch instead of idling
        with annotating(self.tracer is not None and self.tracer.annotate):
            held = None                 # dispatched, not yet read back
            while True:
                with annotation("repro.engine.wait"):
                    got = self._next_batch(held)
                if got is None:         # closed and drained
                    if held is not None:
                        self._finish(held)
                    return
                new = self._dispatch(*got) if got[0] else None
                if held is not None:
                    self._finish(held)
                held = new

    def _next_batch(self, held: "Optional[_InFlight]"):
        """Block until a batch can form -> (batch, index, t_deq); an empty
        batch once ``held`` is done before the next batch formed; None
        once closed and drained."""
        with self._lock:
            # the batch deadline anchors to the OLDEST queued request's
            # enqueue time (a request waits at most max_wait_s before
            # dispatch), and each wake-up reads the clock ONCE -- the
            # old loop re-read time.monotonic() on every predicate
            # evaluation and anchored the deadline to worker wake-up,
            # so a request arriving into an idle worker could dispatch
            # immediately (deadline already stale) and the measured
            # wait was unknowable
            while len(self._queue) < self.batch_size and not self._stop:
                if held is not None and held.ready():
                    return [], None, None
                now = time.monotonic()
                if self._queue:
                    deadline = self._queue[0][2] + self.max_wait_s
                    if now >= deadline:
                        break
                    wait = deadline - now
                else:
                    wait = self.max_wait_s
                if held is not None:    # look again when it may be done
                    wait = min(wait, _HELD_POLL_S)
                self._lock.wait(timeout=wait)
            if self._stop and not self._queue:
                return None
            t_deq = time.monotonic()
            batch = self._queue[: self.batch_size]
            del self._queue[: len(batch)]
            # snapshot under the lock: a hot swap after this point
            # applies to the NEXT batch, this one finishes on `index`.
            # _serving publishes the snapshots of the batches in flight,
            # so a concurrent donate-ingest knows these buffers are read
            index = self.index
            self._serving.append(index)
            self._inflight += len(batch)
        return batch, index, t_deq

    def _dispatch(self, batch, index, t_deq) -> "_InFlight":
        """Form one batch and dispatch its search -> the batch in flight.
        A failing search must not kill the worker: every queued and
        in-flight future would strand (resolve only by caller timeout) --
        the error is kept and fails this batch's futures alone."""
        d = _InFlight(batch, index, t_deq)
        try:
            with annotation("repro.engine.batch_form"):
                # one t_deq for the whole batch: the queue-wait each
                # metric and trace span reports is (t_deq - enqueue),
                # same clock read; one lock acquisition for the whole
                # batch's waits
                self._h_wait.observe_many([t_deq - it[2] for it in batch])
                self._h_occupancy.observe(len(batch) / self.batch_size)
                qs = np.stack([it[0] for it in batch])
                pad = self.batch_size - qs.shape[0]
                if pad:
                    qs = np.concatenate(
                        [qs, np.zeros((pad, qs.shape[1]), qs.dtype)])
                kwargs = {"merge": self.merge} if self.merge else {}
                if self.max_postings is not None:
                    kwargs["max_postings"] = self.max_postings
                if any(it[4] for it in batch):
                    # ONE dispatch subtree shared by every profiled
                    # request in the batch (they share the dispatch);
                    # the index annotates its phases into it when it
                    # supports the profile kwarg
                    d.prof = ProfileNode(
                        "dispatch", batch_size=len(batch),
                        engine=self.engine, k=self.k, page=self.page,
                        **({} if self.group is None
                           else {"group": self.group}))
                    if _accepts_profile(index):
                        kwargs["profile"] = d.prof
                d.t_dispatch = time.monotonic()
            # the dispatch span runs to the end of this batch's readback,
            # past the next batch's dispatch (spans of two batches overlap)
            d.span = annotation("repro.engine.dispatch")
            d.span.__enter__()
            with self.compile_watch.region(
                    "engine.dispatch",
                    sig=(qs.shape, str(qs.dtype), self.engine, self.k,
                         self.page, self.merge or "gather")):
                d.ids, d.scores = index.search(
                    jnp.asarray(qs), k=self.k, page=self.page,
                    trim=self.trim, engine=self.engine, **kwargs)
        except Exception as exc:  # noqa: BLE001 - fwd to futures
            d.error = exc
        return d

    def _finish(self, d: "_InFlight") -> None:
        """Read one dispatched batch back and resolve its futures."""
        ids = scores = None
        try:
            if d.error is None:
                try:
                    with annotation("repro.engine.readback"):
                        ids, scores = np.asarray(d.ids), np.asarray(d.scores)
                except Exception as exc:  # noqa: BLE001 - fwd to futures
                    d.error = exc
            t_done = time.monotonic()
            if d.span is not None:
                d.span.__exit__(None, None, None)
            if d.prof is not None and d.error is None:
                d.prof.duration_s = t_done - d.t_dispatch
            with annotation("repro.engine.resolve"):
                self._resolve(d.batch, d.error, ids, scores, d.prof,
                              d.t_deq, d.t_dispatch, t_done)
        finally:
            with self._lock:
                self._inflight -= len(d.batch)
                # by identity: two batches in flight may share a snapshot
                for i, s in enumerate(self._serving):
                    if s is d.index:
                        del self._serving[i]
                        break

    def _resolve(self, batch, error, ids, scores, prof, t_deq, t_dispatch,
                 t_done) -> None:
        """Record the dispatch and resolve the batch's futures."""
        self._h_dispatch.observe(t_done - t_dispatch)
        # record spans BEFORE resolving futures: resolving fires the
        # submitter's done-callback, which finishes the trace -- and a
        # slow log serializes the span list at finish time (the tracer
        # ring holds live traces, so it never noticed ordering; the slow
        # log does)
        for _, _, t_enq, tr, _ in batch:
            if not tr:          # NULL_TRACE: skip the kwargs builds
                continue
            tr.span("queue_wait", t0=t_enq, t1=t_deq, group=self.group)
            tr.span("batch_form", t0=t_deq, t1=t_dispatch,
                    batch_size=len(batch), group=self.group)
            tr.span("dispatch", t0=t_dispatch, t1=t_done,
                    group=self.group, batch_size=len(batch),
                    **({} if error is None else {"error": repr(error)}))
        if error is not None:
            for _, fut, _, _, _ in batch:
                if not fut.done():
                    fut.set_exception(error)
            self._c_failed.inc(len(batch))
            return
        for i, (_, fut, t_enq, _, want) in enumerate(batch):
            if fut.done():      # caller may have cancelled
                continue
            if want:
                # per-request root over the shared dispatch subtree; all
                # phase bounds are SHARED clock reads, so queue_wait +
                # batch_form + dispatch tile the total exactly
                root = ProfileNode(
                    "query", t_done - t_enq, engine=self.engine, k=self.k,
                    page=self.page,
                    **({} if self.group is None else {"group": self.group}))
                root.child("queue_wait", t_deq - t_enq)
                root.child("batch_form", t_dispatch - t_deq,
                           batch_size=len(batch))
                root.children.append(prof)
                fut.set_result((ids[i], scores[i], root.to_dict()))
            else:
                fut.set_result((ids[i], scores[i]))
        self._c_completed.inc(len(batch))
        self._c_kernel_path.inc()   # one dispatch on `engine`
