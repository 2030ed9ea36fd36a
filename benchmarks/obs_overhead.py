"""Observability overhead: serving QPS with instrumentation on vs off.

    PYTHONPATH=src python -m benchmarks.obs_overhead \
        [--docs 8000] [--queries 64] [--max-overhead 0.03] [--json out]

The obs layer (:mod:`repro.obs`) promises to be cheap enough to leave on
in production: counters/histograms are a dict lookup + bisect per record,
and tracing admits one query in 16 by default (counter-based, no RNG).
This bench measures the promise instead of asserting it by construction.
The same query load runs through two ``BatchedSearchEngine``s over one
shared index:

* **off** -- ``MetricsRegistry(enabled=False)`` and no tracer: every
  record collapses to a single attribute check, the configuration a
  latency-critical deployment would pick;
* **on**  -- an enabled registry plus a ``Tracer`` at the default 1/16
  sampling rate: the configuration everything else in this repo runs
  with;
* **full** -- everything v2 added on top of ``on``: a tail-sampled
  :class:`~repro.obs.slowlog.SlowLog` (every request gets a span
  skeleton), a :class:`~repro.obs.compile_watch.CompileWatch` wrapping
  the dispatch seams, and ``profile=True`` on every submit
  (per-phase ``block_until_ready`` fences + a profile tree per
  request); v3 adds a concurrent 50ms poller hammering the device-side
  surfaces while the pass serves (``device_bytes`` + ``node_stats`` +
  ``stats()`` -- the health/telemetry scrape loop).  Pinned under a
  separate, looser ``--max-overhead-full`` bar (default 5%): the
  _profile fences genuinely serialize the dispatch phases, so this
  config buys attribution with a real (bounded) cost.

Configs are timed interleaved (off, on, off, on, ...) over many SHORT
passes with the order alternating each repeat, and per-query
submit-to-done latencies ride along (done-callback clock stamps, the
benchmarks/cluster_scale.py technique).  The headline overhead is
``min(best-pass wall ratio, median pair ratio)``: on a contended host
individual pass walls swing far more than the effect being measured
(observed up to 3x under CPU-stolen neighbours), but contention only
ever ADDS time, so with enough short passes the min-over-repeats walls
converge on the uncontended cost of each config -- the quantity the <3%
bar is about -- and the median of per-pair ratios cross-checks it (a
REAL regression shows in both; a one-off stall corrupts at most one).
Keeping passes short (one queue drain, default ~2 batches) maximises
the chance each config lands a stall-free pass; the per-pair wall
ratios are recorded in the JSON row for noise forensics.  The run
asserts the combined overhead stays under ``--max-overhead`` (default
3%, the PR 6 acceptance bar), re-measuring up to twice before failing.

Rows *append* to ``artifacts/BENCH_obs_scale.json`` (one run entry per
invocation) so the overhead trajectory accumulates across PRs.
``benchmarks/run.py`` invokes this in a subprocess like the other serving
benches.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ARGS = argparse.ArgumentParser()
_ARGS.add_argument("--docs", type=int, default=8000)
_ARGS.add_argument("--features", type=int, default=64)
_ARGS.add_argument("--queries", type=int, default=32)
_ARGS.add_argument("--batch-size", type=int, default=16)
_ARGS.add_argument("--page", type=int, default=320)
_ARGS.add_argument("--engine", default="codes")
_ARGS.add_argument("--repeats", type=int, default=80)
_ARGS.add_argument("--rounds", type=int, default=1,
                   help="times the query set is replayed per timed pass "
                        "(keep passes short: the min-ratio estimator "
                        "wants many chances at a stall-free pass)")
_ARGS.add_argument("--sample", type=float, default=1.0 / 16,
                   help="trace sampling rate for the on-config (default "
                        "1/16, the Tracer default)")
_ARGS.add_argument("--max-overhead", type=float, default=0.03,
                   help="acceptance bar: relative QPS loss of the "
                        "on-config (default 3%%)")
_ARGS.add_argument("--max-overhead-full", type=float, default=0.05,
                   help="acceptance bar for the full config (metrics + "
                        "tracer + slow log + compile watch + profile "
                        "trees on every request; default 5%%)")
_ARGS.add_argument("--json", default=os.path.join(
    os.path.dirname(__file__), "..", "artifacts", "BENCH_obs_scale.json"))

if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    _early = _ARGS.parse_args()
    from repro.launch.hostdev import use_compile_cache

    use_compile_cache()

import numpy as np


def _one_pass(engine, queries, rounds=1, timeout=120.0, profile=False,
              poll=None):
    """Submit the query set ``rounds`` times, wait, -> (wall_s, per-query
    latencies).  ``poll`` (full config) is called concurrently every
    50ms for the duration of the pass -- the stats/health/device-
    telemetry poller a monitored deployment runs against a serving
    engine, at ~200x a production scrape cadence."""
    import threading

    stop = poller = None
    if poll is not None:
        stop = threading.Event()

        def _poll_loop():
            while not stop.wait(0.05):
                poll()

        poller = threading.Thread(target=_poll_loop, daemon=True)
        poller.start()
    try:
        lats = []
        futs = []
        t0 = time.perf_counter()
        for _ in range(rounds):
            for q in queries:
                t_sub = time.perf_counter()
                f = (engine.submit(q, profile=True) if profile
                     else engine.submit(q))
                f.add_done_callback(lambda _f, t_sub=t_sub: lats.append(
                    time.perf_counter() - t_sub))
                futs.append(f)
        for f in futs:
            f.result(timeout=timeout)
        wall = time.perf_counter() - t0
    finally:
        if stop is not None:
            stop.set()
            poller.join()
    # done-callbacks land after result() unblocks; settle for a full set
    deadline = time.perf_counter() + 5.0
    while len(lats) < len(futs) and time.perf_counter() < deadline:
        time.sleep(0.001)
    return wall, lats


def run(n_docs=8000, n_features=64, n_queries=32, batch_size=16, page=320,
        engine="codes", repeats=80, rounds=1, sample=1.0 / 16,
        max_overhead=0.03, max_overhead_full=0.05):
    import jax.numpy as jnp
    from benchmarks.common import latency_percentiles
    from repro.core import (CombinedEncoder, IntervalEncoder,
                            RoundingEncoder, VectorIndex)
    from repro.core.rerank import normalize
    from repro.obs import CompileWatch, MetricsRegistry, SlowLog, Tracer
    from repro.serve.engine import BatchedSearchEngine

    rng = np.random.default_rng(0)
    V = np.asarray(normalize(jnp.asarray(
        rng.normal(size=(n_docs, n_features)).astype(np.float32))))
    queries = V[rng.choice(n_docs, size=n_queries, replace=False)]
    index = VectorIndex.build(
        V, CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1)))

    # every pass must run the same number of batches in both configs: trim
    # the load to whole batches and let the worker wait for FULL batches
    # (generous max_wait_s) -- otherwise partial-batch luck quantises the
    # pass wall by +-1 dispatch and drowns the effect being measured
    batch_size = min(batch_size, n_queries)
    n_queries = max(batch_size, n_queries - n_queries % batch_size)
    queries = queries[:n_queries]
    # isolated registries: the off-engine must not share series with the
    # on-engine, and neither should pollute the process default registry
    full_reg = MetricsRegistry()
    engines = {
        "off": BatchedSearchEngine(
            index, batch_size=batch_size, max_wait_s=1.0, page=page,
            trim=None, engine=engine,
            metrics=MetricsRegistry(enabled=False)),
        "on": BatchedSearchEngine(
            index, batch_size=batch_size, max_wait_s=1.0, page=page,
            trim=None, engine=engine, metrics=MetricsRegistry(),
            tracer=Tracer(sample=sample)),
        "full": BatchedSearchEngine(
            index, batch_size=batch_size, max_wait_s=1.0, page=page,
            trim=None, engine=engine, metrics=full_reg,
            tracer=Tracer(sample=sample),
            slowlog=SlowLog(threshold_s=0.1, metrics=full_reg),
            compile_watch=CompileWatch(metrics=full_reg)),
    }
    profiled = {"full"}             # submits carry profile=True
    names = ("off", "on", "full")

    # v3: the full config also pays the DEVICE-side plane while serving --
    # a concurrent poller hitting the index byte accounting, the engine
    # stats rollup, and the per-device node_stats every 50ms (still
    # ~200x a production scrape cadence).  The <5% bar therefore covers
    # the WHOLE plane, polled hot.
    from repro.obs import device_bytes, node_stats

    def _poll_full(_eng=engines["full"]):
        device_bytes(_eng.index, reconcile=False)
        node_stats(_eng)
        _eng.stats()

    pollers = {"full": _poll_full}

    def _measure():
        best = {name: (np.inf, []) for name in engines}
        walls = {name: [] for name in engines}
        for rep in range(repeats):                    # interleaved triples,
            r = rep % len(names)                      # order rotating so no
            order = names[r:] + names[:r]             # config always runs
            for name in order:                        # cache-warm last
                wall, lats = _one_pass(engines[name], queries,
                                       rounds=rounds,
                                       profile=name in profiled,
                                       poll=pollers.get(name))
                walls[name].append(wall)
                if wall < best[name][0]:
                    best[name] = (wall, lats)
        return best, walls

    rows = []
    total_q = n_queries * rounds
    try:
        for name, eng in engines.items():             # compile + warm all
            _one_pass(eng, queries, profile=name in profiled)
        # the true cost (~1%) sits well under the bar, but so does the
        # noise floor of wall timing on a contended host: combine two
        # estimators (a REAL >bar regression shows in both) and
        # re-measure before failing on what is usually a neighbour's
        # CPU burst
        def _estimate(name):
            ratios = [x / off
                      for off, x in zip(walls["off"], walls[name])]
            return (min(best[name][0] / best["off"][0],
                        float(np.median(ratios))) - 1.0, ratios)

        for attempt in range(3):
            best, walls = _measure()
            overhead, ratios = _estimate("on")
            overhead_full, ratios_full = _estimate("full")
            if ((overhead < max_overhead
                 and overhead_full < max_overhead_full) or attempt == 2):
                break
            print(f"# overhead on={overhead:.2%} full={overhead_full:.2%} "
                  f"over a bar -- re-measuring (attempt {attempt + 2}/3)")
    finally:
        for eng in engines.values():
            eng.close()

    for name in names:
        wall, lats = best[name]
        tails = latency_percentiles(lats)
        rows.append({
            "config": name,
            "qps": total_q / wall,
            "per_query_s": wall / total_q,
            "latency": tails,
            "sample": 0.0 if name == "off" else sample,
            "batch_size": batch_size,
            "engine": engine,
            "n_docs": n_docs,
            "n_features": n_features,
            "page": page,
        })
        print(f"obs_overhead,{wall / total_q * 1e6:.0f},"
              f"config={name};qps={total_q / wall:.1f};"
              f"p50_ms={tails['p50_ms']:.2f};p99_ms={tails['p99_ms']:.2f}")

    # headline = min(best-pass ratio, median pair ratio): contention only
    # adds time, so the minima converge on each config's uncontended cost
    # (see module docstring), and the median cross-checks it
    rows.append({"config": "overhead", "relative_overhead": overhead,
                 "best_pass_ratio": best["on"][0] / best["off"][0],
                 "median_pair_ratio": float(np.median(ratios)),
                 "pair_ratios": [float(r) for r in ratios],
                 "max_overhead": max_overhead, "repeats": repeats,
                 "rounds": rounds})
    rows.append({"config": "overhead_full",
                 "relative_overhead": overhead_full,
                 "best_pass_ratio": best["full"][0] / best["off"][0],
                 "median_pair_ratio": float(np.median(ratios_full)),
                 "pair_ratios": [float(r) for r in ratios_full],
                 "max_overhead": max_overhead_full, "repeats": repeats,
                 "rounds": rounds})
    print(f"obs_overhead,0,overhead={overhead * 100:.2f}%;"
          f"bar={max_overhead * 100:.0f}%")
    print(f"obs_overhead,0,overhead_full={overhead_full * 100:.2f}%;"
          f"bar={max_overhead_full * 100:.0f}%")
    assert overhead < max_overhead, (
        f"instrumentation overhead {overhead:.1%} exceeds the "
        f"{max_overhead:.0%} acceptance bar "
        f"(pair ratios: {[round(r, 4) for r in ratios]})")
    assert overhead_full < max_overhead_full, (
        f"full-instrumentation overhead {overhead_full:.1%} (profile + "
        f"slow log + compile watch) exceeds the {max_overhead_full:.0%} "
        f"acceptance bar "
        f"(pair ratios: {[round(r, 4) for r in ratios_full]})")
    return rows


def main(argv_args=None):
    args = argv_args or _ARGS.parse_args()
    rows = run(n_docs=args.docs, n_features=args.features,
               n_queries=args.queries, batch_size=args.batch_size,
               page=args.page, engine=args.engine, repeats=args.repeats,
               rounds=args.rounds, sample=args.sample,
               max_overhead=args.max_overhead,
               max_overhead_full=args.max_overhead_full)
    out = os.path.abspath(args.json)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # append, never overwrite: the overhead trajectory accumulates across PRs
    doc = {"bench": "obs_overhead", "runs": []}
    if os.path.exists(out):
        try:
            with open(out) as f:
                prev = json.load(f)
            if isinstance(prev.get("runs"), list):
                doc = prev
        except (OSError, ValueError):
            pass  # unreadable history: start a fresh file rather than crash
    doc["runs"].append({"rows": rows})
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"# appended run {len(doc['runs'])} to {out}")


if __name__ == "__main__":
    main(_early)
