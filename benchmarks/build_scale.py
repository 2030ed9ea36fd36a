"""Index-build wall-clock and ingest throughput vs shard count.

    PYTHONPATH=src python -m benchmarks.build_scale [--shards 1,2,4] \
        [--ingest-batch 256] [--json out]

The third leg of the shard/replica/build scaling triangle: PR 1 measured
query QPS vs shards, PR 2 vs replicas; this measures *construction*.  For
every shard count the same corpus is built twice -- via the reference path
(``VectorIndex.build`` on one device, then ``from_index`` partitioning) and
via the on-device one-program SPMD build (``build_sharded``) -- and then a
stream of ``add_documents`` batches measures incremental ingest throughput
(docs/s through the append-segment path, including the post-ingest search
validating the new docs are live).

Rows *append* to ``artifacts/BENCH_build_scale.json`` (one run entry per
invocation) so the build-time trajectory accumulates across PRs.  On one
host fanned out into virtual devices the numbers measure protocol/dispatch
overhead, not scaling -- real-device runs should append theirs to the same
file.  ``benchmarks/run.py`` invokes this in a subprocess (the
virtual-device flag must precede jax initialisation).
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
_ARGS.add_argument("--queries", type=int, default=32,
                   help="sanity-search batch validating the built index")
_ARGS.add_argument("--ingest-batch", type=int, default=256)
_ARGS.add_argument("--ingest-batches", type=int, default=4)
_ARGS.add_argument("--repeats", type=int, default=3)
_ARGS.add_argument("--json", default=os.path.join(
    os.path.dirname(__file__), "..", "artifacts", "BENCH_build_scale.json"))


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


def run(shard_counts, n_docs=20000, n_features=64, n_queries=32,
        ingest_batch=256, ingest_batches=4, repeats=3):
    import jax
    import jax.numpy as jnp
    from repro.core import (CombinedEncoder, IntervalEncoder, RoundingEncoder,
                            VectorIndex)
    from repro.core.rerank import normalize
    from repro.dist.shard_index import ShardedVectorIndex
    from repro.launch.mesh import make_shard_mesh

    encoder = CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1))
    rng = np.random.default_rng(0)
    topics = rng.normal(size=(32, n_features)).astype(np.float32)
    assign = rng.integers(0, len(topics), size=n_docs)
    V = topics[assign] + 0.7 * rng.normal(
        size=(n_docs, n_features)).astype(np.float32)
    V = np.asarray(normalize(jnp.asarray(V)))
    extra = topics[rng.integers(0, len(topics),
                                size=ingest_batch * ingest_batches)]
    extra = extra + 0.7 * rng.normal(size=extra.shape).astype(np.float32)
    queries = V[rng.choice(n_docs, size=n_queries, replace=False)]

    def leaves(sidx):
        return (sidx.vectors, sidx.codes, sidx.df_table,
                sidx.seg_vectors, sidx.seg_codes)

    rows = []
    for s in shard_counts:
        if s > len(jax.devices()):
            # on stdout AND in the JSON: a silently missing row would read
            # as "covered" in the accumulated build-time trajectory
            print(f"build_scale,shards={s},0,"
                  f"SKIPPED_only_{len(jax.devices())}_devices")
            rows.append({"shards": s, "skipped": True,
                         "reason": f"only {len(jax.devices())} devices"})
            continue
        mesh = make_shard_mesh(s)

        def on_device():
            idx = ShardedVectorIndex.build_sharded(V, mesh, encoder=encoder)
            jax.block_until_ready(leaves(idx))
            return idx

        def reference():
            idx = ShardedVectorIndex.from_index(
                VectorIndex.build(V, encoder), mesh)
            jax.block_until_ready(leaves(idx))
            return idx

        best_dev, best_ref = np.inf, np.inf
        for timer_target in range(repeats + 1):          # first = compile+warm
            t0 = time.perf_counter()
            sidx = on_device()
            dt = time.perf_counter() - t0
            if timer_target:
                best_dev = min(best_dev, dt)
            t0 = time.perf_counter()
            reference()
            dt = time.perf_counter() - t0
            if timer_target:
                best_ref = min(best_ref, dt)

        # incremental ingest throughput: a batch stream through the
        # append-segment path, closed by a search so the timing covers the
        # full hot-add-to-visible cycle (the ES refresh story).  Every
        # cumulative segment width hits its own jit cache entry, so the
        # warm-up pass must replay the EXACT batch/search shape sequence
        # the timed pass will see -- anything less leaves a trace+compile
        # inside dt_ingest and the recorded docs/s becomes compile noise.
        def ingest_cycle():
            grown = sidx
            for b in range(ingest_batches):
                grown = grown.add_documents(
                    extra[b * ingest_batch:(b + 1) * ingest_batch])
                jax.block_until_ready(leaves(grown))
            jax.block_until_ready(grown.search(jnp.asarray(queries), k=10))
            return grown
        ingest_cycle()                                    # compile + warm
        t0 = time.perf_counter()
        grown = ingest_cycle()
        dt_ingest = time.perf_counter() - t0
        added = ingest_batch * ingest_batches
        assert grown.n_ids == n_docs + added

        rows.append({
            "shards": s,
            "build_on_device_s": best_dev,
            "build_from_index_s": best_ref,
            "speedup": best_ref / best_dev,
            "ingest_docs_per_s": added / dt_ingest,
            "ingest_batch": ingest_batch,
            "n_docs": n_docs,
            "n_features": n_features,
        })
        print(f"build_scale,shards={s},{best_dev * 1e6:.0f},"
              f"on_device_s={best_dev:.3f};from_index_s={best_ref:.3f};"
              f"ingest_dps={added / dt_ingest:.0f}")
    return rows


def main(argv_args=None):
    args = argv_args or _parse()
    rows = run(args.shard_counts, n_docs=args.docs, n_features=args.features,
               n_queries=args.queries, ingest_batch=args.ingest_batch,
               ingest_batches=args.ingest_batches, repeats=args.repeats)
    out = os.path.abspath(args.json)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # append, never overwrite: the build-time trajectory accumulates
    doc = {"bench": "build_scale", "runs": []}
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
