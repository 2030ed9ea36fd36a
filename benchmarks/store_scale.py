"""Durability cost curves: ingest throughput vs translog policy, recovery
time vs translog length.

    PYTHONPATH=src python -m benchmarks.store_scale \
        [--shards 1,4] [--docs 20000] [--ingest-batch 64] [--batches 8] \
        [--json out]

Two questions the store subsystem (repro/store) makes measurable:

1. **What does durability cost on the ingest path?**  The same hot-add
   stream runs three ways: no store (the PR 3 memory-only baseline),
   ``durability=async`` (translog append, buffered), and
   ``durability=request`` (fsync before every ack, the ES default).  The
   spread between the three is the price of the write-ahead log and of
   the fsync respectively.
2. **What does recovery cost, and how does it scale with the translog?**
   ``recover()`` = restore the latest commit point + replay the
   uncommitted ops; recovery wall time is measured at increasing
   translog lengths (0, then after each batch of ops) against a fixed
   commit, plus once more after a fresh commit (zero replay -- the
   commit-restore floor).  The gap between the floor and the replay
   curve is the argument for the maintenance daemon's post-compaction
   commits trimming the log.  The fresh commit also logs its honest
   cost -- ``bytes_written`` vs ``bytes_total`` -- so the
   content-addressed O(changed) claim rides in this artifact too
   (benchmarks/segment_scale.py has the full bytes-vs-generation curve).

Rows *append* to ``artifacts/BENCH_store_scale.json`` (one run entry per
invocation) so the trajectory accumulates across PRs.  ``benchmarks/
run.py`` invokes this in a subprocess (the virtual-device flag must
precede jax initialisation).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

# XLA_FLAGS must be set before the first jax import
_ARGS = argparse.ArgumentParser()
_ARGS.add_argument("--shards", default="1,4",
                   help="comma-separated shard counts (each its own mesh)")
_ARGS.add_argument("--docs", type=int, default=20000)
_ARGS.add_argument("--features", type=int, default=64)
_ARGS.add_argument("--ingest-batch", type=int, default=64)
_ARGS.add_argument("--batches", type=int, default=8,
                   help="ingest batches per policy (also the recovery-curve "
                        "translog lengths)")
_ARGS.add_argument("--queries", type=int, default=32,
                   help="queries for the recovered-vs-live parity assert")
_ARGS.add_argument("--repeats", type=int, default=3)
_ARGS.add_argument("--json", default=os.path.join(
    os.path.dirname(__file__), "..", "artifacts", "BENCH_store_scale.json"))


def _parse():
    args = _ARGS.parse_args()
    args.shard_counts = sorted(
        {int(s) for s in args.shards.split(",") if s.strip()})
    return args


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch.hostdev import force_host_devices, use_compile_cache

    _early = _parse()
    force_host_devices(max(_early.shard_counts))
    use_compile_cache()

import time

import numpy as np


def run(shard_counts, n_docs=20000, n_features=64, ingest_batch=64,
        n_batches=8, repeats=3, n_queries=32):
    import jax
    from repro.dist.shard_index import ShardedVectorIndex
    from repro.launch.mesh import make_shard_mesh
    from repro.store import Store, recover

    rng = np.random.default_rng(0)
    V = rng.normal(size=(n_docs, n_features)).astype(np.float32)
    Q = V[rng.choice(n_docs, size=n_queries, replace=False)]
    batches = [rng.normal(size=(ingest_batch, n_features)).astype(np.float32)
               for _ in range(n_batches)]

    rows = []
    for s in shard_counts:
        if s > len(jax.devices()):
            print(f"store_scale,shards={s},0,"
                  f"SKIPPED_only_{len(jax.devices())}_devices")
            rows.append({"shards": s, "skipped": True,
                         "reason": f"only {len(jax.devices())} devices"})
            continue
        mesh = make_shard_mesh(s)
        base = ShardedVectorIndex.build_sharded(V, mesh)

        # ---- ingest throughput vs durability policy ------------------
        for policy in ("none", "async", "request"):
            best, best_lat = np.inf, []
            for _ in range(repeats):
                tmp = tempfile.mkdtemp(prefix="bench_store_")
                try:
                    if policy == "none":
                        idx = base
                    else:
                        store = Store(tmp, durability=policy)
                        idx = store.open_index(base)
                    idx.add_documents(batches[0])       # compile warm-up
                    # per-op wall = the ack latency an ingest client sees
                    # (durability=request pays its fsync INSIDE this window)
                    lats = []
                    t0 = time.perf_counter()
                    run_idx = idx
                    for b in batches:
                        t1 = time.perf_counter()
                        run_idx = run_idx.add_documents(b)
                        lats.append(time.perf_counter() - t1)
                    jax.block_until_ready(run_idx.seg_vectors)
                    wall = time.perf_counter() - t0
                    if wall < best:
                        best, best_lat = wall, lats
                    if policy != "none":
                        store.close()
                finally:
                    shutil.rmtree(tmp, ignore_errors=True)
            total = n_batches * ingest_batch
            from benchmarks.common import latency_percentiles

            tails = latency_percentiles(best_lat)
            rows.append({
                "mode": "ingest", "shards": s, "durability": policy,
                "docs_per_s": total / best, "latency": tails,
                "ingest_batch": ingest_batch,
                "n_batches": n_batches, "n_docs": n_docs,
                "n_features": n_features,
            })
            print(f"store_scale,shards={s},{best / total * 1e6:.0f},"
                  f"mode=ingest;durability={policy};"
                  f"docs_per_s={total / best:.0f};"
                  f"p50_ms={tails['p50_ms']:.2f};p99_ms={tails['p99_ms']:.2f}")

        # ---- recovery time vs translog length ------------------------
        tmp = tempfile.mkdtemp(prefix="bench_store_")
        try:
            store = Store(tmp, durability="async")
            idx = store.open_index(base)            # commit point at seq 0
            for n_ops in range(n_batches + 1):
                if n_ops:
                    idx = idx.add_documents(batches[n_ops - 1])
                    store.translog.sync()
                best, samples = np.inf, []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    rec, seq = recover(tmp, make_shard_mesh(s))
                    jax.block_until_ready(rec.vectors)
                    samples.append(time.perf_counter() - t0)
                    best = min(best, samples[-1])
                assert seq == n_ops and rec.n_ids == idx.n_ids
                from benchmarks.common import latency_percentiles
                rows.append({
                    "mode": "recover", "shards": s, "translog_ops": n_ops,
                    "recover_s": best,
                    "latency": latency_percentiles(samples),
                    "n_ids": int(idx.n_ids),
                    "n_docs": n_docs, "n_features": n_features,
                })
                print(f"store_scale,shards={s},{best * 1e6:.0f},"
                      f"mode=recover;translog_ops={n_ops};"
                      f"recover_s={best:.4f}")
            # the commit-restore floor: fresh commit, zero replay -- and
            # the honest commit cost: bytes actually written vs bytes the
            # commit references (content-addressed blobs re-reference
            # unchanged parts, so written << total past generation 1)
            store.commit(idx)
            reg = store.metrics
            written = reg.value("store.commit.last_bytes_written")
            total_b = reg.value("store.commit.last_bytes_total")
            rows.append({
                "mode": "commit", "shards": s,
                "bytes_written": written, "bytes_total": total_b,
                "n_ids": int(idx.n_ids), "n_docs": n_docs,
                "n_features": n_features,
            })
            print(f"store_scale,shards={s},{written:.0f},"
                  f"mode=commit;bytes_written={written:.0f};"
                  f"bytes_total={total_b:.0f}")
            best, samples = np.inf, []
            for _ in range(repeats):
                t0 = time.perf_counter()
                rec, _ = recover(tmp, make_shard_mesh(s))
                jax.block_until_ready(rec.vectors)
                samples.append(time.perf_counter() - t0)
                best = min(best, samples[-1])
            rows.append({
                "mode": "recover", "shards": s, "translog_ops": 0,
                "post_commit": True, "recover_s": best,
                "latency": latency_percentiles(samples),
                "n_ids": int(idx.n_ids), "n_docs": n_docs,
                "n_features": n_features,
            })
            print(f"store_scale,shards={s},{best * 1e6:.0f},"
                  f"mode=recover;post_commit=1;recover_s={best:.4f}")
            # recovered-vs-live bit-parity: the durability analogue of
            # cluster_scale's failover parity assert
            li, ls = idx.search(Q, k=10, page=2 * idx.n_ids)
            ri, rs = rec.search(Q, k=10, page=2 * rec.n_ids)
            assert np.array_equal(np.asarray(li), np.asarray(ri)) and \
                np.array_equal(np.asarray(ls), np.asarray(rs)), \
                "recovered index diverged from live"
            store.close()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return rows


def main(argv_args=None):
    args = argv_args or _parse()
    rows = run(args.shard_counts, n_docs=args.docs,
               n_features=args.features, ingest_batch=args.ingest_batch,
               n_batches=args.batches, repeats=args.repeats,
               n_queries=args.queries)
    out = os.path.abspath(args.json)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # append, never overwrite: the trajectory accumulates across PRs
    doc = {"bench": "store_scale", "runs": []}
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
