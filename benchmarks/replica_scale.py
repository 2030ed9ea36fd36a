"""QPS/latency vs (shards, replicas) for the replica serving tier.

    PYTHONPATH=src python -m benchmarks.replica_scale \
        [--grid 1x1,2x1,2x2,4x2] [--merge gather,stream] [--json out]

The paper scales reads the way Elasticsearch does: doc-shards partition the
corpus (PR 1, benchmarks/shard_scale.py), replica shards multiply the
serving copies.  This measures the second axis: for every ``SxR`` cell the
same corpus/index is sharded over S devices, replicated R times, and a
fixed query batch is timed through ``ShardedVectorIndex.search`` under each
merge transport -- QPS, per-query latency, and P@10 vs the brute-force gold
standard (exactly 1.0 while ``page >= n_docs``: replication and the merge
transport are throughput knobs, never a quality trade).

Rows *append* to ``artifacts/BENCH_replica_scale.json`` (one run entry per
invocation) so the perf trajectory accumulates across PRs.  On one host
fanned out into virtual devices the numbers measure protocol overhead, not
scaling -- real-device runs should append theirs to the same file.
``benchmarks/run.py`` invokes this in a subprocess (the virtual-device flag
must precede jax initialisation).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# XLA_FLAGS must be set before the first jax import
_ARGS = argparse.ArgumentParser()
_ARGS.add_argument("--grid", default="1x1,2x1,2x2,4x2",
                   help="comma-separated SxR cells (shards x replicas)")
_ARGS.add_argument("--merge", default="gather,stream",
                   help="comma-separated merge transports to time")
_ARGS.add_argument("--docs", type=int, default=20000)
_ARGS.add_argument("--features", type=int, default=64)
_ARGS.add_argument("--queries", type=int, default=64)
_ARGS.add_argument("--page", type=int, default=320)
_ARGS.add_argument("--engine", default="codes")
_ARGS.add_argument("--repeats", type=int, default=3)
_ARGS.add_argument("--json", default=os.path.join(
    os.path.dirname(__file__), "..", "artifacts", "BENCH_replica_scale.json"))


def _parse():
    args = _ARGS.parse_args()
    cells = []
    for cell in args.grid.split(","):
        s, r = cell.lower().split("x")
        cells.append((int(s), int(r)))
    args.cells = sorted(set(cells))
    args.merges = [m.strip() for m in args.merge.split(",") if m.strip()]
    return args


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch.hostdev import force_host_devices, use_compile_cache

    _early = _parse()
    force_host_devices(max(s * r for s, r in _early.cells))
    use_compile_cache()

import time

import numpy as np


def run(cells, merges=("gather", "stream"), n_docs=20000, n_features=64,
        n_queries=64, page=320, engine="codes", repeats=3):
    import jax
    import jax.numpy as jnp
    from repro.core import (CombinedEncoder, IntervalEncoder, RoundingEncoder,
                            VectorIndex, precision_at_k)
    from repro.core.rerank import normalize
    from repro.launch.mesh import make_shard_mesh

    # topic-mixture vectors, same rationale as benchmarks/shard_scale.py:
    # phase-1 bucket matches must carry signal for a meaningful P@10
    rng = np.random.default_rng(0)
    topics = rng.normal(size=(32, n_features)).astype(np.float32)
    assign = rng.integers(0, len(topics), size=n_docs)
    V = topics[assign] + 0.7 * rng.normal(
        size=(n_docs, n_features)).astype(np.float32)
    V = np.asarray(normalize(jnp.asarray(V)))
    queries = V[rng.choice(n_docs, size=n_queries, replace=False)]
    index = VectorIndex.build(
        V, CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1)))
    gold_ids, _ = index.gold_topk(queries, 10)

    rows = []
    for s, r in cells:
        if s * r > len(jax.devices()):
            # on stdout AND in the JSON: a silently missing cell would read
            # as "covered" in the accumulated perf trajectory
            print(f"replica_scale,shards={s}x{r},0,"
                  f"SKIPPED_only_{len(jax.devices())}_devices")
            rows.append({"shards": s, "replicas": r, "skipped": True,
                         "reason": f"only {len(jax.devices())} devices"})
            continue
        sidx = index.shard(make_shard_mesh(s, r))
        for merge in merges:
            search = lambda: sidx.search(jnp.asarray(queries), k=10,
                                         page=page, engine=engine,
                                         merge=merge)
            jax.block_until_ready(search())                   # compile + warm
            best = np.inf
            for _ in range(repeats):
                t0 = time.perf_counter()
                ids, _scores = search()
                jax.block_until_ready((ids, _scores))
                best = min(best, time.perf_counter() - t0)
            p10 = float(np.asarray(precision_at_k(ids, gold_ids)).mean())
            # per-query tails from batch-1 singles (benchmarks/shard_scale.py
            # rationale: batched timing is throughput, singles are latency)
            from benchmarks.common import latency_percentiles

            single = lambda q: sidx.search(jnp.asarray(q[None]), k=10,
                                           page=page, engine=engine,
                                           merge=merge)
            jax.block_until_ready(single(queries[0]))         # batch-1 compile
            lat = []
            for q in queries:
                t0 = time.perf_counter()
                jax.block_until_ready(single(q))
                lat.append(time.perf_counter() - t0)
            tails = latency_percentiles(lat)
            rows.append({
                "shards": s,
                "replicas": r,
                "merge": merge,
                "qps": n_queries / best,
                "per_query_s": best / n_queries,
                "latency": tails,
                "p10": p10,
                "engine": engine,
                "n_docs": n_docs,
                "n_features": n_features,
                "page": page,
            })
            print(f"replica_scale,shards={s}x{r},"
                  f"{best / n_queries * 1e6:.0f},"
                  f"merge={merge};qps={n_queries / best:.1f};p10={p10:.4f};"
                  f"p50_ms={tails['p50_ms']:.2f};p99_ms={tails['p99_ms']:.2f}")
    return rows


def main(argv_args=None):
    args = argv_args or _parse()
    rows = run(args.cells, merges=args.merges, n_docs=args.docs,
               n_features=args.features, n_queries=args.queries,
               page=args.page, engine=args.engine, repeats=args.repeats)
    out = os.path.abspath(args.json)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # append, never overwrite: the (S, R) trajectory accumulates across PRs
    doc = {"bench": "replica_scale", "runs": []}
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
