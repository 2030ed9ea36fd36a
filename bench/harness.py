"""Run one cell once: set up the system under test from the seed, drive
the traffic through its served path for the window, read the metrics, and
hold the window's answers to the plain reference.

Only the system under test and its spans, counters and program names come
from the program (``src/repro``); data, traffic, the reference, the trace
reduction and the metric arithmetic are this directory's.
"""

from __future__ import annotations

import gc
import os
import shutil
import sys
import threading
import time
from types import SimpleNamespace
from typing import Callable, Optional

import numpy as np

from bench import check, corpus, loadgen, registry

TRACE_DIR = os.path.join(registry.ROOT, ".bench_traces")
TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCount:
    """Counts backend compiles and persistent-cache loads while armed."""

    _live: "Optional[CompileCount]" = None
    _installed = False

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.armed = False
        if not CompileCount._installed:
            import jax.monitoring as mon

            mon.register_event_duration_secs_listener(CompileCount._duration)
            mon.register_event_listener(CompileCount._event)
            CompileCount._installed = True
        CompileCount._live = self

    @staticmethod
    def _duration(event: str, duration: float, **_):
        w = CompileCount._live
        if w and w.armed and event.endswith("backend_compile_duration"):
            w.compiles += 1

    @staticmethod
    def _event(event: str, **_):
        w = CompileCount._live
        if w and w.armed and event.endswith("compilation_cache/cache_hits"):
            w.cache_hits += 1


class System:
    """The program as a configuration deploys it: mesh, on-chip corpus,
    sharded index and the batched engine in front of it."""

    def __init__(self, cfg: dict, seed: int, *, tracer=None, fault=None):
        import jax

        from repro.core import (CombinedEncoder, IntervalEncoder,
                                RoundingEncoder, TrimFilter)
        from repro.dist.shard_index import ShardedVectorIndex
        from repro.launch.mesh import make_shard_mesh
        from repro.obs.compile_watch import CompileWatch
        from repro.obs.metrics import MetricsRegistry
        from repro.serve.engine import BatchedSearchEngine

        mesh = make_shard_mesh(cfg["n_shards"], cfg["n_replicas"])
        mix = cfg["corpus"]
        x = corpus.corpus(seed, cfg["n_docs"], cfg["n_features"],
                          mix["n_topics"], mix["noise"], mesh)
        enc = cfg["encoder"]
        encoder = CombinedEncoder(RoundingEncoder(enc["rounding_precision"]),
                                  IntervalEncoder(enc["interval_width"]))
        self.index = ShardedVectorIndex.build_sharded(x, mesh,
                                                      encoder=encoder)
        del x
        jax.block_until_ready(self.index)
        self.metrics = MetricsRegistry()
        served = fault(self.index) if fault else self.index
        self.engine = BatchedSearchEngine(
            served, batch_size=cfg["batch_size"],
            max_wait_s=cfg["max_wait_s"], k=cfg["k"], page=cfg["page"],
            trim=TrimFilter(cfg["trim"]), engine=cfg["engine"],
            merge=cfg["merge"], metrics=self.metrics, tracer=tracer,
            compile_watch=CompileWatch(enabled=False))

    def dispatch_hist(self):
        h = self.metrics.histogram("engine.dispatch.latency_s")
        return h.count, h.sum

    def close(self) -> None:
        self.engine.close()
        self.engine = self.index = None
        gc.collect()


def _profile_window(t_open: float, seconds: float, out: str):
    """Trace ``TRACE_SECONDS`` in the middle of the window (its own
    thread, so the clients never wait on the profiler)."""
    import jax

    span = min(TRACE_SECONDS, seconds / 2)
    time.sleep(max(0.0, t_open + (seconds - span) / 2 - time.perf_counter()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        from bench.trace_reduce import WINDOW_SPAN

        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            time.sleep(span)
    finally:
        jax.profiler.stop_trace()


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, name: str, fault: Optional[Callable] = None,
             on_program: Optional[Callable] = None,
             on_reference: Optional[Callable] = None) -> dict:
    """One run of the resolved cell ``spec`` (``registry.resolve``) -> the
    result object, ``checks`` last.

    ``fault`` wraps the index the engine serves (the tests plant faults
    with it).  ``on_program(system, queries)`` and ``on_reference(ref,
    queries, readings)`` run after the window, before the program's state
    and then the reference are freed; the dicts they return go into the
    result's ``readings`` (``bench/controls.py`` reads its controls so)."""
    import jax

    from bench import trace_reduce

    cfg, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    devices = jax.devices()[:cell["chips"]]
    counter = CompileCount()
    tracer = None
    if trace:
        from repro.obs.tracing import Tracer

        tracer = Tracer(capacity=1, sample=1.0 / (1 << 30), annotate=True)
    t_sys = time.perf_counter()
    system = System(cfg, seed, tracer=tracer, fault=fault)
    t_built = time.perf_counter()
    mix = cfg["corpus"]
    pool = corpus.query_pool(seed, traffic["pool"], cfg["n_features"],
                             mix["n_topics"], mix["noise"])
    gen = loadgen.LoadGen(traffic, system.engine.submit, pool, seed,
                          cfg["batch_size"])
    box: dict = {}
    prof = None
    trace_out = os.path.join(TRACE_DIR, name)

    def on_open(t0):
        nonlocal prof
        box["setup_s"] = t0 - t_start
        log(f"setup: {t_sys - t_start:.2f} s to jax and the chips, "
            f"{t_built - t_sys:.2f} s corpus and index, "
            f"{t0 - t_built:.2f} s query pool and warm-up")
        box["hist0"] = system.dispatch_hist()
        counter.armed = True
        if trace:
            shutil.rmtree(trace_out, ignore_errors=True)
            prof = threading.Thread(target=_profile_window,
                                    args=(t0, seconds, trace_out))
            prof.start()

    window = gen.run(seconds, on_open=on_open)
    counter.armed = False
    if prof is not None:
        prof.join()
    hist1 = system.dispatch_hist()
    mem_peak = memory_peak(devices)
    log(f"window: {len(window.records)} requests sent, "
        f"{window.ok_in_window} answered in {window.seconds:.3f} s; "
        f"compiles in window {counter.compiles}, cache loads "
        f"{counter.cache_hits}; peak HBM per chip {mem_peak} B")

    recs = window.records
    failed = sum(r.error is not None for r in recs)
    unanswered = sum(np.isnan(r.done) for r in recs)
    done = [r for r in recs if r.error is None and not np.isnan(r.done)]
    reduction = trace_reduce.load(trace_out) if trace else None
    if trace:
        shutil.rmtree(trace_out, ignore_errors=True)
        for c in reduction.chips:
            qp = c.in_modules("jit__query_phase", reduction.dispatches)
            kernels = sum(trace_reduce.is_kernel(op)
                          for op in c.ops_within(qp))
            log(f"trace, chip {c.index}: {len(reduction.dispatches)} "
                f"complete dispatches holding {len(qp)} query-phase runs "
                f"and {kernels} kernel events; "
                f"{len(c.in_modules('jit__query_phase'))} query-phase "
                f"runs in the {reduction.window_s:.4f} s window")
    kind = devices[0].device_kind
    # what the metric readers (bench/metrics/<name>.py) see
    data = SimpleNamespace(
        cfg=cfg, cell=cell, traffic=traffic, window=window,
        setup_s=box["setup_s"],
        latencies_s=np.asarray([r.done - r.due for r in done]),
        dispatches=hist1[0] - box["hist0"][0],
        dispatch_s=hist1[1] - box["hist0"][1],
        trace=reduction, device_kind=kind, chips=len(devices))
    group = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in group:
        v = registry.reader(m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the answers: a seeded sample of the window's, against the reference
    rng = np.random.default_rng([int(seed), 0xC4EC])
    n_s = min(traffic["sample"], len(done))
    sample = [done[i] for i in sorted(
        rng.choice(len(done), n_s, replace=False).tolist())]
    queries = pool[[r.row for r in sample]]
    extra = {}
    if on_program is not None:
        extra.update(on_program(system, queries))
    system.close()
    del system, gen
    gc.collect()
    if sample:
        t_ref = time.perf_counter()
        ref = registry.reference(cfg["reference"]).Reference(cfg, seed)
        numbers = check.compare(ref, queries,
                                np.stack([r.ids for r in sample]),
                                np.stack([r.scores for r in sample]), cfg)
        log(f"reference: {len(sample)} sampled answers "
            f"({int(numbers['ambiguous'])} ambiguous) in "
            f"{time.perf_counter() - t_ref:.2f} s")
        if on_reference is not None:
            extra.update(on_reference(ref, queries, extra))
        del ref
        gc.collect()
    else:                       # nothing answered: nothing can be right
        numbers = dict.fromkeys(("bad_ids", "rank_gap", "score_err"),
                                float("nan"))
    numbers.update(failed=float(failed), unanswered=float(unanswered))
    correct, checks = check.verdict(numbers, cfg["limits"])
    out = {
        "correct": bool(correct),
        "attempted": len(recs),
        "failed": int(failed + unanswered),
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": mem_peak},
    }
    if reduction is not None:
        out["device"].update(busy_s=reduction.busy_s,
                             window_s=reduction.window_s)
        out["breakdown"] = {"device_ops": reduction.device_ops(),
                            "idle_gaps": reduction.idle_gaps()}
    if extra:
        out["readings"] = extra
    out["checks"] = checks
    return out
