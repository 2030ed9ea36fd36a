"""QPS vs shard count for the doc-sharded index (the paper's horizontal axis).

    PYTHONPATH=src python -m benchmarks.shard_scale [--shards 1,2,4] [--json out]

The paper scales by adding Elasticsearch doc-shards; this measures the same
trajectory on one host fanned out into virtual devices.  For every shard
count: build one corpus/index, doc-shard it, run batched queries, report
QPS and P@10 vs the brute-force gold standard (which is exactly 1.0 while
``page >= n_docs`` -- sharding is a throughput axis, not a quality trade).

Emits ``artifacts/BENCH_shard_scale.json`` so the perf trajectory
accumulates across PRs; ``benchmarks/run.py`` invokes this in a subprocess
(the virtual-device flag must precede jax initialisation).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# XLA_FLAGS must be set before the first jax import
_ARGS = argparse.ArgumentParser()
_ARGS.add_argument("--shards", default="1,2,4")
_ARGS.add_argument("--docs", type=int, default=20000)
_ARGS.add_argument("--features", type=int, default=64)
_ARGS.add_argument("--queries", type=int, default=64)
_ARGS.add_argument("--page", type=int, default=320)
_ARGS.add_argument("--engine", default="codes")
_ARGS.add_argument("--repeats", type=int, default=3)
_ARGS.add_argument("--json", default=os.path.join(
    os.path.dirname(__file__), "..", "artifacts", "BENCH_shard_scale.json"))


def _parse():
    args = _ARGS.parse_args()
    args.shard_counts = sorted({int(s) for s in args.shards.split(",")})
    return args


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch.hostdev import force_host_devices, use_compile_cache

    _early = _parse()
    force_host_devices(max(_early.shard_counts))
    use_compile_cache()

import time

import numpy as np


def run(shard_counts, n_docs=20000, n_features=64, n_queries=64, page=320,
        engine="codes", repeats=3):
    import jax
    import jax.numpy as jnp
    from repro.core import (CombinedEncoder, IntervalEncoder, RoundingEncoder,
                            VectorIndex, precision_at_k)
    from repro.core.rerank import normalize
    from repro.launch.mesh import make_shard_mesh

    # topic-mixture vectors (cheap stand-in for the LSA pipeline): docs
    # cluster around topic directions, so phase-1 bucket matches carry
    # signal the way real LSA features do -- pure gaussians would make
    # every cosine ~0 and measure only the encoder's noise floor
    rng = np.random.default_rng(0)
    topics = rng.normal(size=(32, n_features)).astype(np.float32)
    assign = rng.integers(0, len(topics), size=n_docs)
    V = topics[assign] + 0.7 * rng.normal(
        size=(n_docs, n_features)).astype(np.float32)
    V = np.asarray(normalize(jnp.asarray(V)))
    queries = V[rng.choice(n_docs, size=n_queries, replace=False)]
    # P1+I0.1: the bucket scale benchmarks/common.py established for
    # unit vectors at this feature count (P2 cells are too fine)
    index = VectorIndex.build(
        V, CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1)))
    gold_ids, _ = index.gold_topk(queries, 10)

    rows = []
    for s in shard_counts:
        if s > len(jax.devices()):
            # on stdout AND in the JSON: a silently missing row would read
            # as "covered" in the accumulated perf trajectory
            print(f"shard_scale,shards={s},0,"
                  f"SKIPPED_only_{len(jax.devices())}_devices")
            rows.append({"shards": s, "skipped": True,
                         "reason": f"only {len(jax.devices())} devices"})
            continue
        idx = index if s == 1 else index.shard(make_shard_mesh(s))
        search = lambda: idx.search(jnp.asarray(queries), k=10, page=page,
                                    engine=engine)
        jax.block_until_ready(search())                       # compile + warm
        best = np.inf
        for _ in range(repeats):
            t0 = time.perf_counter()
            ids, _scores = search()
            jax.block_until_ready((ids, _scores))
            best = min(best, time.perf_counter() - t0)
        p10 = float(np.asarray(precision_at_k(ids, gold_ids)).mean())
        # per-query latency tails: the batched timing above is throughput;
        # singles (batch-1 searches, their own compile warmed first) give
        # the per-query distribution the stats layer reports at runtime
        from benchmarks.common import latency_percentiles

        single = lambda q: idx.search(jnp.asarray(q[None]), k=10, page=page,
                                      engine=engine)
        jax.block_until_ready(single(queries[0]))             # batch-1 compile
        lat = []
        for q in queries:
            t0 = time.perf_counter()
            jax.block_until_ready(single(q))
            lat.append(time.perf_counter() - t0)
        tails = latency_percentiles(lat)
        rows.append({
            "shards": s,
            "qps": n_queries / best,
            "per_query_s": best / n_queries,
            "latency": tails,
            "p10": p10,
            "engine": engine,
            "n_docs": n_docs,
            "n_features": n_features,
            "page": page,
        })
        print(f"shard_scale,shards={s},{best / n_queries * 1e6:.0f},"
              f"qps={n_queries / best:.1f};p10={p10:.4f};"
              f"p50_ms={tails['p50_ms']:.2f};p99_ms={tails['p99_ms']:.2f}")
    return rows


def main(argv_args=None):
    args = argv_args or _parse()
    rows = run(args.shard_counts, n_docs=args.docs, n_features=args.features,
               n_queries=args.queries, page=args.page, engine=args.engine,
               repeats=args.repeats)
    out = os.path.abspath(args.json)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"bench": "shard_scale", "rows": rows}, f, indent=2)
    print(f"# wrote {out}")


if __name__ == "__main__":
    main(_early)
