"""QPS vs (concurrent streams x replica groups) for the cluster control plane.

    PYTHONPATH=src python -m benchmarks.cluster_scale \
        [--grid 1x1,2x2,4x2] [--streams 1,4] [--json out]

The replica tier (benchmarks/replica_scale.py) measures the data plane:
one batcher fronting the whole mesh, parallelism materialising inside a
single SPMD batch.  This measures the CONTROL plane: ``ClusterEngine``
runs one independent batcher per replica group, so R groups serve R
batches concurrently -- the ES arrangement where concurrent QPS scales
with replica count.  For every ``SxR`` cell and stream count N, N client
threads each push a stream of queries through the cluster (stream
affinity pins a client to a group; overflow spills least-loaded), and the
wall time gives cluster QPS.  With R > 1 each cell is additionally
re-timed with one replica group marked down -- the failover cost curve --
and the down-run asserts result parity against the healthy run.

Rows *append* to ``artifacts/BENCH_cluster_scale.json`` (one run entry
per invocation) so the perf trajectory accumulates across PRs.  On one
host fanned out into virtual devices the numbers measure protocol
overhead, not scaling -- real-device runs should append theirs to the
same file.  ``benchmarks/run.py`` invokes this in a subprocess (the
virtual-device flag must precede jax initialisation).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# XLA_FLAGS must be set before the first jax import
_ARGS = argparse.ArgumentParser()
_ARGS.add_argument("--grid", default="1x1,2x2,4x2",
                   help="comma-separated SxR cells (shards x replica groups)")
_ARGS.add_argument("--streams", default="1,4",
                   help="comma-separated concurrent client-stream counts")
_ARGS.add_argument("--docs", type=int, default=20000)
_ARGS.add_argument("--features", type=int, default=64)
_ARGS.add_argument("--queries", type=int, default=32,
                   help="queries per client stream")
_ARGS.add_argument("--page", type=int, default=320)
_ARGS.add_argument("--engine", default="codes")
_ARGS.add_argument("--batch-size", type=int, default=8)
_ARGS.add_argument("--repeats", type=int, default=3)
_ARGS.add_argument("--json", default=os.path.join(
    os.path.dirname(__file__), "..", "artifacts", "BENCH_cluster_scale.json"))


def _parse():
    args = _ARGS.parse_args()
    cells = []
    for cell in args.grid.split(","):
        s, r = cell.lower().split("x")
        cells.append((int(s), int(r)))
    args.cells = sorted(set(cells))
    args.stream_counts = sorted(
        {int(n) for n in args.streams.split(",") if n.strip()})
    return args


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch.hostdev import force_host_devices, use_compile_cache

    _early = _parse()
    force_host_devices(max(s * r for s, r in _early.cells))
    use_compile_cache()

import threading
import time

import numpy as np


def _drive(cluster, queries, n_streams, timeout=300.0):
    """N client threads, each a pinned stream of queries -> (wall_s, results
    keyed (stream, i), per-query latencies).  Latency is submit-to-done per
    future (a done-callback stamps the clock in the completing worker), so
    it includes queue wait under real contention -- the same quantity the
    engine's queue-wait + dispatch histograms decompose."""
    results = {}
    latencies = []
    errors = []

    def client(sid):
        try:
            futs = []
            for q in queries:
                t_sub = time.perf_counter()
                f = cluster.submit(q, stream=sid)
                f.add_done_callback(
                    lambda _f, t_sub=t_sub: latencies.append(
                        time.perf_counter() - t_sub))
                futs.append(f)
            for i, f in enumerate(futs):
                results[(sid, i)] = f.result(timeout=timeout)
        except Exception as exc:  # noqa: BLE001 - surfaced to the caller
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(sid,))
               for sid in range(n_streams)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    # done-callbacks run in the completing worker AFTER result() unblocks;
    # settle so the sample set is complete before percentiles are taken
    deadline = time.perf_counter() + 5.0
    while (len(latencies) < n_streams * len(queries)
           and time.perf_counter() < deadline):
        time.sleep(0.001)
    return wall, results, latencies


def run(cells, stream_counts=(1, 4), n_docs=20000, n_features=64,
        n_queries=32, page=320, engine="codes", batch_size=8, repeats=3):
    import jax
    import jax.numpy as jnp
    from repro.cluster import ClusterEngine
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
            print(f"cluster_scale,shards={s}x{r},0,"
                  f"SKIPPED_only_{len(jax.devices())}_devices")
            rows.append({"shards": s, "replicas": r, "skipped": True,
                         "reason": f"only {len(jax.devices())} devices"})
            continue
        sidx = index.shard(make_shard_mesh(s, r))
        cluster = ClusterEngine(sidx, batch_size=batch_size, k=10, page=page,
                                trim=None, engine=engine)
        try:
            scenarios = [("healthy", None)]
            if r > 1:
                scenarios.append(("one_down", 0))
            baseline = {}
            for scenario, down in scenarios:
                if down is not None:
                    cluster.mark_down(down)
                for n_streams in stream_counts:
                    _drive(cluster, queries[: min(4, n_queries)],
                           n_streams)                 # compile + warm
                    best, res, lat = np.inf, None, []
                    for _ in range(repeats):
                        wall, got, lats = _drive(cluster, queries, n_streams)
                        if wall < best:
                            best, res, lat = wall, got, lats
                    total_q = n_streams * n_queries
                    from benchmarks.common import latency_percentiles

                    tails = latency_percentiles(lat)
                    ids = jnp.asarray(
                        np.stack([res[(0, i)][0] for i in range(n_queries)]))
                    p10 = float(np.asarray(
                        precision_at_k(ids, gold_ids)).mean())
                    if scenario == "healthy":
                        baseline[n_streams] = res
                    else:
                        # failover parity: every (stream, i) result must
                        # match the healthy cluster bit for bit
                        ref = baseline[n_streams]
                        assert all(
                            np.array_equal(res[key][0], ref[key][0])
                            and np.array_equal(res[key][1], ref[key][1])
                            for key in res), "one_down diverged from healthy"
                    rows.append({
                        "shards": s,
                        "replicas": r,
                        "scenario": scenario,
                        "n_streams": n_streams,
                        "qps": total_q / best,
                        "per_query_s": best / total_q,
                        "latency": tails,
                        "p10": p10,
                        "engine": engine,
                        "batch_size": batch_size,
                        "n_docs": n_docs,
                        "n_features": n_features,
                        "page": page,
                    })
                    print(f"cluster_scale,shards={s}x{r},"
                          f"{best / total_q * 1e6:.0f},"
                          f"scenario={scenario};streams={n_streams};"
                          f"qps={total_q / best:.1f};p10={p10:.4f};"
                          f"p50_ms={tails['p50_ms']:.2f};"
                          f"p99_ms={tails['p99_ms']:.2f}")
                if down is not None:
                    cluster.mark_up(down)
        finally:
            cluster.close()
    return rows


def main(argv_args=None):
    args = argv_args or _parse()
    rows = run(args.cells, stream_counts=args.stream_counts,
               n_docs=args.docs, n_features=args.features,
               n_queries=args.queries, page=args.page, engine=args.engine,
               batch_size=args.batch_size, repeats=args.repeats)
    out = os.path.abspath(args.json)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # append, never overwrite: the trajectory accumulates across PRs
    doc = {"bench": "cluster_scale", "runs": []}
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
