"""_profile cost + per-phase latency breakdown at serving scale.

    PYTHONPATH=src python -m benchmarks.profile_overhead \
        [--docs 8000] [--queries 32] [--shards 2] [--max-overhead 0.05] \
        [--json out]

The companion to :mod:`benchmarks.obs_overhead`: that bench pins the
cost of the always-on plane (metrics + sampled tracing); this one pins
the cost of asking *why* -- every request served with the FULL v2
instrumentation (metrics + tracer + tail-sampled slow log + compile
watch + ``profile=True`` execution trees) against a bare engine over
the same sharded index.  The _profile fences (``block_until_ready``
between encode / phase-1 / merge / rescore) genuinely serialize the
dispatch phases, so unlike the passive plane this cost is real; the
acceptance bar is 5% (``--max-overhead``).

The same min(best-pass ratio, median pair ratio) estimator as
obs_overhead handles host contention, with up to two re-measures before
failing.  Alongside the overhead row, the run aggregates every profile
tree it collected into per-phase p50/p99 wall times (queue_wait,
batch_form, dispatch, encode, phase1, merge_select, rescore) -- the
serving-latency decomposition the JSON trajectory tracks across PRs.

Rows *append* to ``artifacts/BENCH_profile_scale.json`` (one run entry
per invocation).  ``benchmarks/run.py`` invokes this in a subprocess
like the other virtual-device benches.
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
_ARGS.add_argument("--engine", default="fused")
_ARGS.add_argument("--shards", type=int, default=2)
_ARGS.add_argument("--repeats", type=int, default=60)
_ARGS.add_argument("--max-overhead", type=float, default=0.05,
                   help="acceptance bar: relative QPS loss of serving "
                        "every request fully instrumented with a profile "
                        "tree (default 5%%)")
_ARGS.add_argument("--json", default=os.path.join(
    os.path.dirname(__file__), "..", "artifacts",
    "BENCH_profile_scale.json"))

if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    _early = _ARGS.parse_args()
    # the device fan-out must precede the first jax import
    from repro.launch.hostdev import force_host_devices, use_compile_cache

    force_host_devices(_early.shards)
    use_compile_cache()

import numpy as np


def _one_pass(engine, queries, profile=False, timeout=120.0):
    """Submit the query set once, wait -> (wall_s, profile trees)."""
    t0 = time.perf_counter()
    futs = [engine.submit(q, profile=True) if profile else engine.submit(q)
            for q in queries]
    out = [f.result(timeout=timeout) for f in futs]
    wall = time.perf_counter() - t0
    return wall, [r[2] for r in out] if profile else []


def _walk_phases(tree, acc):
    """Accumulate every timed node's duration under its phase name."""
    d = tree.get("duration_s")
    if d is not None and tree.get("name") not in ("query", "cluster.query"):
        acc.setdefault(tree["name"], []).append(d)
    for c in tree.get("children", ()):
        _walk_phases(c, acc)


def run(n_docs=8000, n_features=64, n_queries=32, batch_size=16, page=320,
        engine="fused", n_shards=2, repeats=60, max_overhead=0.05):
    import jax.numpy as jnp
    from repro.core import CombinedEncoder, IntervalEncoder, RoundingEncoder
    from repro.core.rerank import normalize
    from repro.dist.shard_index import ShardedVectorIndex
    from repro.launch.mesh import make_shard_mesh
    from repro.obs import CompileWatch, MetricsRegistry, SlowLog, Tracer
    from repro.serve.engine import BatchedSearchEngine

    rng = np.random.default_rng(0)
    V = np.asarray(normalize(jnp.asarray(
        rng.normal(size=(n_docs, n_features)).astype(np.float32))))
    queries = V[rng.choice(n_docs, size=n_queries, replace=False)]
    mesh = make_shard_mesh(n_shards)
    index = ShardedVectorIndex.build_sharded(
        V, mesh, encoder=CombinedEncoder(RoundingEncoder(1),
                                         IntervalEncoder(0.1)))

    batch_size = min(batch_size, n_queries)
    n_queries = max(batch_size, n_queries - n_queries % batch_size)
    queries = queries[:n_queries]
    full_reg = MetricsRegistry()
    engines = {
        "off": BatchedSearchEngine(
            index, batch_size=batch_size, max_wait_s=1.0, page=page,
            trim=None, engine=engine,
            metrics=MetricsRegistry(enabled=False)),
        "profile": BatchedSearchEngine(
            index, batch_size=batch_size, max_wait_s=1.0, page=page,
            trim=None, engine=engine, metrics=full_reg,
            tracer=Tracer(sample=1.0 / 16),
            slowlog=SlowLog(threshold_s=0.1, metrics=full_reg),
            compile_watch=CompileWatch(metrics=full_reg)),
    }
    phases: dict = {}

    def _measure():
        best = {name: np.inf for name in engines}
        walls = {name: [] for name in engines}
        for rep in range(repeats):
            order = (("off", "profile") if rep % 2
                     else ("profile", "off"))
            for name in order:
                wall, trees = _one_pass(engines[name], queries,
                                        profile=name == "profile")
                for t in trees:
                    _walk_phases(t, phases)
                walls[name].append(wall)
                best[name] = min(best[name], wall)
        return best, walls

    try:
        for name, eng in engines.items():             # compile + warm both
            _one_pass(eng, queries, profile=name == "profile")
        for attempt in range(3):
            best, walls = _measure()
            ratios = [p / off
                      for off, p in zip(walls["off"], walls["profile"])]
            overhead = min(best["profile"] / best["off"],
                           float(np.median(ratios))) - 1.0
            if overhead < max_overhead or attempt == 2:
                break
            print(f"# overhead {overhead:.2%} over the bar -- "
                  f"re-measuring (attempt {attempt + 2}/3)")
    finally:
        for eng in engines.values():
            eng.close()

    rows = []
    for name in ("off", "profile"):
        rows.append({
            "config": name,
            "qps": n_queries / best[name],
            "per_query_s": best[name] / n_queries,
            "batch_size": batch_size,
            "engine": engine,
            "n_shards": n_shards,
            "n_docs": n_docs,
            "n_features": n_features,
            "page": page,
        })
        print(f"profile_overhead,{best[name] / n_queries * 1e6:.0f},"
              f"config={name};qps={n_queries / best[name]:.1f}")

    def _q(vals, frac):
        s = sorted(vals)
        return s[min(len(s) - 1, int(frac * len(s)))]

    phase_row = {"config": "phases", "per_phase": {}}
    for name in sorted(phases):
        vals = phases[name]
        p50, p99 = _q(vals, 0.5), _q(vals, 0.99)
        phase_row["per_phase"][name] = {
            "p50_ms": p50 * 1e3, "p99_ms": p99 * 1e3, "n": len(vals)}
        print(f"profile_overhead,{p50 * 1e6:.0f},"
              f"phase={name};p50_ms={p50 * 1e3:.3f};p99_ms={p99 * 1e3:.3f}")
    rows.append(phase_row)

    rows.append({"config": "overhead", "relative_overhead": overhead,
                 "best_pass_ratio": best["profile"] / best["off"],
                 "median_pair_ratio": float(np.median(ratios)),
                 "pair_ratios": [float(r) for r in ratios],
                 "max_overhead": max_overhead, "repeats": repeats})
    print(f"profile_overhead,0,overhead={overhead * 100:.2f}%;"
          f"bar={max_overhead * 100:.0f}%")
    assert overhead < max_overhead, (
        f"full _profile instrumentation overhead {overhead:.1%} exceeds "
        f"the {max_overhead:.0%} acceptance bar "
        f"(pair ratios: {[round(r, 4) for r in ratios]})")
    return rows


def main(argv_args=None):
    args = argv_args or _ARGS.parse_args()
    rows = run(n_docs=args.docs, n_features=args.features,
               n_queries=args.queries, batch_size=args.batch_size,
               page=args.page, engine=args.engine, n_shards=args.shards,
               repeats=args.repeats, max_overhead=args.max_overhead)
    out = os.path.abspath(args.json)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # append, never overwrite: the trajectory accumulates across PRs
    doc = {"bench": "profile_overhead", "runs": []}
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
