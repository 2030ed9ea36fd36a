"""Segment-lifecycle cost curves: ingest latency, search tails, and
commit bytes under the generational index.

    PYTHONPATH=src python -m benchmarks.segment_scale \
        [--shards 1,4] [--docs 8000] [--ingest-batch 64] [--batches 12] \
        [--seal-threshold 128] [--json out]

Three questions the segment story (PR 7) makes measurable:

1. **Does sealing keep ingest flat?**  The same hot-add stream runs
   three ways: ``flat`` (``seal_threshold=None`` -- the old single
   append buffer, whose growth path is the full-rebuild stall the
   segment refactor exists to kill), ``seal`` (generational sealing, no
   merges), and ``seal+merge`` (sealing plus a
   :class:`~repro.cluster.maintenance.TieredMergePolicy` pass after each
   batch -- the maintenance daemon's plan, applied synchronously so the
   bench is deterministic).  Every row carries the FULL per-batch
   latency trace (``lat_ms_trace``) plus ``max_ms``: the no-stall claim
   is checkable from the artifact, not asserted by prose.  Merge passes
   are timed separately (``merge_ms_total``) -- in production they run
   off the query path on the daemon thread.
2. **What do merges buy search?**  After ingest, the same query batch is
   timed against the end state of each config; ``seal`` serves N sealed
   generations, ``seal+merge`` serves the folded tiers.  p50/p99 per
   call, same corpus, same engine.
3. **Are commits O(changed)?**  A durable store commits after every
   ingest batch; each generation's row records ``bytes_written`` vs
   ``bytes_total`` straight from the store's own metrics
   (content-addressed blobs: unchanged segments are re-referenced, so
   written stays ~flat while total grows with the corpus -- the ES
   incremental-snapshot shape).  The section ends with a kill ->
   ``recover()`` -> bit-parity assert against the live index, so the
   numbers are only ever reported for a store that provably restores.

Rows *append* to ``artifacts/BENCH_segment_scale.json`` (one run entry
per invocation).  ``benchmarks/run.py`` invokes this in a subprocess
(the virtual-device flag must precede jax initialisation); ``make
smoke-segments`` runs the quick 4-device config.
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
_ARGS.add_argument("--docs", type=int, default=8000)
_ARGS.add_argument("--features", type=int, default=64)
_ARGS.add_argument("--ingest-batch", type=int, default=64)
_ARGS.add_argument("--batches", type=int, default=12)
_ARGS.add_argument("--seal-threshold", type=int, default=128)
_ARGS.add_argument("--merge-factor", type=int, default=4)
_ARGS.add_argument("--queries", type=int, default=32)
_ARGS.add_argument("--search-calls", type=int, default=24,
                   help="timed search calls per config (the p99 base)")
_ARGS.add_argument("--repeats", type=int, default=3)
_ARGS.add_argument("--json", default=os.path.join(
    os.path.dirname(__file__), "..", "artifacts",
    "BENCH_segment_scale.json"))


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

_CONFIGS = ("flat", "seal", "seal+merge")


def _ingest_pass(base, batches, config, policy):
    """One warm ingest pass -> (index, per-batch latencies, merge seconds,
    merges applied).  Merge passes (seal+merge only) are timed apart from
    the add path, mirroring the daemon running them off the query path."""
    import jax

    idx = base
    lats, merge_s, merges = [], 0.0, 0
    for b in batches:
        t1 = time.perf_counter()
        idx = idx.add_documents(b)
        jax.block_until_ready(idx.seg_vectors)
        lats.append(time.perf_counter() - t1)
        if policy is not None:
            sel = policy.select(idx)
            if sel is not None:
                t2 = time.perf_counter()
                idx = idx.merge_segments(sel["start"], sel["count"])
                jax.block_until_ready(idx.segments[sel["start"]].vectors)
                merge_s += time.perf_counter() - t2
                merges += 1
    return idx, lats, merge_s, merges


def run(shard_counts, n_docs=8000, n_features=64, ingest_batch=64,
        n_batches=12, seal_threshold=128, merge_factor=4, n_queries=32,
        n_search=24, repeats=3):
    import jax
    from repro.cluster.maintenance import TieredMergePolicy
    from repro.dist.shard_index import ShardedVectorIndex
    from repro.launch.mesh import make_shard_mesh
    from repro.store import Store

    from benchmarks.common import latency_percentiles

    rng = np.random.default_rng(0)
    V = rng.normal(size=(n_docs, n_features)).astype(np.float32)
    Q = V[rng.choice(n_docs, size=n_queries, replace=False)]
    batches = [rng.normal(size=(ingest_batch, n_features)).astype(np.float32)
               for _ in range(n_batches)]

    rows = []
    for s in shard_counts:
        if s > len(jax.devices()):
            print(f"segment_scale,shards={s},0,"
                  f"SKIPPED_only_{len(jax.devices())}_devices")
            rows.append({"shards": s, "skipped": True,
                         "reason": f"only {len(jax.devices())} devices"})
            continue
        mesh = make_shard_mesh(s)

        # ---- ingest trace + search tails, per config ------------------
        for config in _CONFIGS:
            thr = None if config == "flat" else seal_threshold
            policy = (TieredMergePolicy(merge_factor=merge_factor)
                      if config == "seal+merge" else None)
            base = ShardedVectorIndex.build_sharded(V, mesh,
                                                    seal_threshold=thr)
            # warm-up pass compiles every generation shape this config
            # will visit, so the timed trace measures the rebuild/data
            # path, not one-time jit compilation
            _ingest_pass(base, batches, config, policy)
            best = None
            for _ in range(repeats):
                idx, lats, merge_s, merges = _ingest_pass(
                    base, batches, config, policy)
                if best is None or sum(lats) < sum(best[1]):
                    best = (idx, lats, merge_s, merges)
            idx, lats, merge_s, merges = best
            total = n_batches * ingest_batch
            tails = latency_percentiles(lats)
            row = {
                "mode": "ingest", "shards": s, "config": config,
                "docs_per_s": total / sum(lats), "latency": tails,
                "max_ms": max(lats) * 1e3,
                "lat_ms_trace": [round(t * 1e3, 3) for t in lats],
                "merge_ms_total": merge_s * 1e3, "merges": merges,
                "n_segments_final": int(getattr(idx, "n_segments", 0)),
                "ingest_batch": ingest_batch, "n_batches": n_batches,
                "seal_threshold": thr, "n_docs": n_docs,
                "n_features": n_features,
            }
            print(f"segment_scale,shards={s},"
                  f"{sum(lats) / total * 1e6:.0f},"
                  f"mode=ingest;config={config};"
                  f"docs_per_s={total / sum(lats):.0f};"
                  f"max_ms={row['max_ms']:.2f};"
                  f"segments={row['n_segments_final']};merges={merges}")

            # search tails against this config's end state
            idx.search(Q, k=10, page=2 * idx.n_ids)        # warm-up
            samples = []
            for _ in range(n_search):
                t1 = time.perf_counter()
                ids, _sc = idx.search(Q, k=10, page=2 * idx.n_ids)
                jax.block_until_ready(ids)
                samples.append(time.perf_counter() - t1)
            st = latency_percentiles(samples)
            row["search"] = st
            rows.append(row)
            print(f"segment_scale,shards={s},"
                  f"{np.mean(samples) * 1e6:.0f},"
                  f"mode=search;config={config};"
                  f"p50_ms={st['p50_ms']:.2f};p99_ms={st['p99_ms']:.2f}")

        # ---- commit bytes vs generation (O(changed) evidence) ---------
        tmp = tempfile.mkdtemp(prefix="bench_segment_")
        try:
            from repro.obs.metrics import MetricsRegistry
            from repro.store import recover

            store = Store(tmp, durability="async",
                          metrics=MetricsRegistry())
            policy = TieredMergePolicy(merge_factor=merge_factor)
            idx = store.open_index(ShardedVectorIndex.build_sharded(
                V, mesh, seal_threshold=seal_threshold))
            reg = store.metrics
            for gen, b in enumerate(batches, start=1):
                idx = idx.add_documents(b)
                sel = policy.select(idx)
                if sel is not None:
                    idx = idx.merge_segments(sel["start"], sel["count"])
                store.commit(idx)
                written = reg.value("store.commit.last_bytes_written")
                total_b = reg.value("store.commit.last_bytes_total")
                rows.append({
                    "mode": "commit", "shards": s, "generation": gen,
                    "merged": sel is not None,
                    "bytes_written": written, "bytes_total": total_b,
                    "n_segments": int(idx.n_segments),
                    "n_ids": int(idx.n_ids),
                    "seal_threshold": seal_threshold,
                    "n_docs": n_docs, "n_features": n_features,
                })
                print(f"segment_scale,shards={s},{written:.0f},"
                      f"mode=commit;generation={gen};"
                      f"bytes_written={written:.0f};"
                      f"bytes_total={total_b:.0f};"
                      f"segments={idx.n_segments}")
            # kill -> recover -> bit-parity: the commit numbers above are
            # only reported for a store that provably restores
            store.translog.sync()
            rec, seq = recover(tmp, make_shard_mesh(s))
            li, ls = idx.search(Q, k=10, page=2 * idx.n_ids)
            ri, rs = rec.search(Q, k=10, page=2 * rec.n_ids)
            assert seq == idx.translog_seq
            assert np.array_equal(np.asarray(li), np.asarray(ri)) and \
                np.array_equal(np.asarray(ls), np.asarray(rs)), \
                "recovered index diverged from live"
            print(f"segment_scale,shards={s},0,mode=recover;parity=ok;"
                  f"seq={seq}")
            store.close()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return rows


def main(argv_args=None):
    args = argv_args or _parse()
    rows = run(args.shard_counts, n_docs=args.docs,
               n_features=args.features, ingest_batch=args.ingest_batch,
               n_batches=args.batches, seal_threshold=args.seal_threshold,
               merge_factor=args.merge_factor, n_queries=args.queries,
               n_search=args.search_calls, repeats=args.repeats)
    out = os.path.abspath(args.json)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # append, never overwrite: the trajectory accumulates across PRs
    doc = {"bench": "segment_scale", "runs": []}
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
