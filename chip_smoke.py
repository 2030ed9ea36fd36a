#!/usr/bin/env python3
"""Smoke test of the served search path on TPU chips.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips of one host

The deployment is the paper's Wikipedia semantic search
(``configs/vectordb_wiki.py``): 4,181,504 unit vectors x LSA-400, page 320,
trim 0.05, k 10, with ``launch/serve.py``'s encoder.  LSA cannot reach
that size, so the corpus is the seeded topic mixture of
``benchmarks/shard_scale.py``, made on the host.

One chip serves its share of the 4-chip deployment, 1,045,376 docs: an
on-device build (``ShardedVectorIndex.build_sharded`` on a 1-chip mesh),
then queries through ``BatchedSearchEngine`` under the ``codes``,
``fused`` and ``fused_int8`` engines, a hot ingest and a delete, and the
same queries again.  Every result is held to an exact top-10 computed on
the host in float64: each returned hit's score must be its float64 cosine
within 1e-5, the fused engines' recall@10 must be within 0.02 of
``codes``', and no deleted doc may come back.  The fused engines must
lower to the compiled Pallas kernel (``tpu_custom_call``).

``--four-chips`` runs only what exists across chips: the full 4,181,504
docs on 4 doc shards against the host reference, and a 2-shard x
2-replica ``ClusterEngine`` at the 1-chip size whose results with one
replica group failed must be bit-identical to the healthy cluster's.

The script exits non-zero, and prints no result line, when JAX finds no
TPU or when any check fails.  Its last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import gc
import itertools
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_WIKI = 4_181_504            # configs/vectordb_wiki.py N_DOCS
N_FEATURES = 400
N_CHIP = N_WIKI // 4          # one chip's share of the 4-chip deployment
N_TOPICS = 32                 # benchmarks/shard_scale.py's mixture
NOISE = 0.7
PAGE, K, TRIM = 320, 10, 0.05
BATCH, N_QUERIES = 128, 384
N_INGEST = 4096
SCORE_TOL = 1e-5
RECALL_GAP = 0.02
ENGINES = ("codes", "fused", "fused_int8")
REF_CHUNK = 1 << 17


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ data
class Mixture:
    """Seeded topic mixture: each row is a topic direction plus Gaussian
    noise (``benchmarks/shard_scale.py``), so cosine neighbourhoods carry
    structure the way LSA vectors do."""

    def __init__(self, seed: int, n_features: int = N_FEATURES):
        self.rng = np.random.default_rng(seed)
        self.topics = self.rng.standard_normal(
            (N_TOPICS, n_features), dtype=np.float32)

    def draw(self, n: int) -> np.ndarray:
        out = np.empty((n, self.topics.shape[1]), np.float32)
        for lo in range(0, n, REF_CHUNK):      # bounded float32 temporaries
            hi = min(n, lo + REF_CHUNK)
            out[lo:hi] = self.rng.standard_normal(
                (hi - lo, self.topics.shape[1]), dtype=np.float32)
            out[lo:hi] *= NOISE
            out[lo:hi] += self.topics[
                self.rng.integers(0, N_TOPICS, size=hi - lo)]
        return out


def unit64(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def exact_topk(docs: np.ndarray, queries: np.ndarray, k: int,
               dead=None) -> np.ndarray:
    """Exact cosine top-k ids over ``docs`` in float64, chunked so the
    float64 copy never exceeds one chunk; ``dead`` ids are excluded."""
    qn = unit64(queries)
    best_s = np.full((len(qn), k), -np.inf)
    best_i = np.zeros((len(qn), k), np.int64)
    for lo in range(0, len(docs), REF_CHUNK):
        s = qn @ unit64(docs[lo:lo + REF_CHUNK]).T
        if dead is not None:
            d = dead[(dead >= lo) & (dead < lo + s.shape[1])]
            s[:, d - lo] = -np.inf
        cat_s = np.concatenate([best_s, s], axis=1)
        cat_i = np.concatenate(
            [best_i, np.broadcast_to(np.arange(lo, lo + s.shape[1]),
                                     s.shape)], axis=1)
        pos = np.argpartition(-cat_s, k - 1, axis=1)[:, :k]
        best_s = np.take_along_axis(cat_s, pos, axis=1)
        best_i = np.take_along_axis(cat_i, pos, axis=1)
    return best_i


def check_hits(name, ids, scores, docs, queries, gold, dead=()):
    """Recall@K against ``gold`` and the largest |score - float64 cosine|
    over every returned hit; fails on a bad score or a deleted doc."""
    assert ids.shape == (len(queries), K), (name, ids.shape)
    assert (ids >= 0).all(), f"{name}: unfilled result slots"
    assert not np.isin(ids, np.asarray(dead, np.int64)).any(), \
        f"{name}: a deleted doc was returned"
    cos = np.einsum("qkn,qn->qk", unit64(docs[ids]), unit64(queries))
    err = float(np.abs(scores.astype(np.float64) - cos).max())
    recall = float(np.mean([len(set(a) & set(b)) / K
                            for a, b in zip(ids.tolist(), gold.tolist())]))
    log(f"  {name}: recall@{K} {recall:.4f}  max |score - float64 cosine| "
        f"{err:.3e}")
    assert err <= SCORE_TOL, f"{name}: score error {err} > {SCORE_TOL}"
    return recall


# ---------------------------------------------------------------- device
def memory_line(jax) -> str:
    parts = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        parts.append(f"chip {d.id}: peak {st.get('peak_bytes_in_use', -1)} "
                     f"B, in use {st.get('bytes_in_use', -1)} B")
    return "; ".join(parts)


def index_bytes(index) -> tuple:
    """(logical index bytes, {chip: index bytes resident on it}), from
    the index's own byte accounting."""
    from repro.obs.device import device_bytes

    acct = device_bytes(index, reconcile=False)
    return acct["total_bytes"], acct["per_device"]


class CompileClock:
    """Sums XLA backend compile seconds (a persistent-cache hit adds 0)."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def serve(engine, queries, submit=None):
    """Submit every query, wait on every future -> (ids, scores, seconds)."""
    submit = submit or engine.submit
    t0 = time.perf_counter()
    futs = [submit(q) for q in queries]
    res = [f.result(timeout=900) for f in futs]
    dt = time.perf_counter() - t0
    return (np.stack([r[0] for r in res]).astype(np.int64),
            np.stack([r[1] for r in res]), dt)


def assert_pallas_lowering(jax, index):
    """The fused engines' phase-1 call lowers to the compiled Mosaic
    kernel at the served shard's shapes on this backend."""
    import jax.numpy as jnp

    from repro.kernels.fused_phase1 import ops as fp_ops

    dp, C = index.codes.shape[1:]
    n = index.vectors.shape[-1]
    S = jax.ShapeDtypeStruct
    fp32 = jax.jit(lambda c, qc, w, lv: fp_ops.fused_phase1(
        c, qc, w, page=PAGE, live=lv)).lower(
        S((dp, C), index.codes.dtype), S((BATCH, C), index.codes.dtype),
        S((BATCH, C), jnp.float32), S((dp,), jnp.bool_))
    int8 = jax.jit(lambda c, sc, zp, q, lv: fp_ops.fused_phase1_quant(
        c, sc, zp, q, page=PAGE, live=lv)).lower(
        S((dp, n), jnp.int8), S((dp,), jnp.float32), S((dp,), jnp.float32),
        S((BATCH, n), jnp.float32), S((dp,), jnp.bool_))
    for name, low in (("fused", fp32), ("fused_int8", int8)):
        assert "tpu_custom_call" in low.as_text(), \
            f"{name}: phase 1 did not lower to the Pallas kernel"
    log("  fused, fused_int8: phase 1 lowers to tpu_custom_call "
        "(compiled Pallas kernel)")


# ---------------------------------------------------------------- phases
def one_chip(jax, clock):
    from repro.core import (CombinedEncoder, IntervalEncoder,
                            RoundingEncoder, TrimFilter)
    from repro.dist.shard_index import ShardedVectorIndex
    from repro.launch.mesh import make_shard_mesh
    from repro.serve.engine import BatchedSearchEngine

    t0 = time.perf_counter()
    mix = Mixture(seed=0)
    docs = mix.draw(N_CHIP)
    queries = mix.draw(N_QUERIES)
    gold = exact_topk(docs, queries, K)
    log(f"data: {N_CHIP} docs x {N_FEATURES} features, {N_QUERIES} "
        f"queries, host float64 reference in "
        f"{time.perf_counter() - t0:.2f}s")

    encoder = CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1))
    c0, t0 = clock.total, time.perf_counter()
    index = ShardedVectorIndex.build_sharded(docs, make_shard_mesh(1),
                                             encoder=encoder)
    jax.block_until_ready(index)
    log(f"build: {time.perf_counter() - t0:.2f}s wall, "
        f"{clock.total - c0:.2f}s of it compiling; index "
        f"{index_bytes(index)[1]} B per chip, "
        f"{index.codes.shape[-1]} code columns")
    log(f"memory after build: {memory_line(jax)}")
    assert_pallas_lowering(jax, index)

    common = dict(batch_size=BATCH, k=K, page=PAGE, trim=TrimFilter(TRIM))

    def serve_all(index, docs, gold, dead, tag):
        recall = {}
        for name in ENGINES:
            eng = BatchedSearchEngine(index, engine=name, **common)
            try:
                c0 = clock.total
                ids, scores, cold = serve(eng, queries)
                compile_s = clock.total - c0
                ids2, scores2, warm = serve(eng, queries)
                dispatches = eng.stats()["kernel_path"].get(name)
            finally:
                eng.close()
            assert np.array_equal(ids, ids2) and np.array_equal(
                scores, scores2), f"{name}: a repeat pass changed results"
            log(f"  {name} [{tag}]: {N_QUERIES} queries, first pass "
                f"{cold:.3f}s ({compile_s:.2f}s compiling), warm pass "
                f"{warm:.3f}s, dispatches {dispatches}")
            recall[name] = check_hits(f"{name} [{tag}]", ids, scores, docs,
                                      queries, gold, dead)
        for name in ("fused", "fused_int8"):
            assert recall[name] >= recall["codes"] - RECALL_GAP, \
                (tag, name, recall)
        return recall

    log("serve (built index):")
    serve_all(index, docs, gold, (), "built")

    # hot ingest of one batch, then deletes: the deleted docs are the
    # current best hits of the first queries, so a stale answer shows
    added = mix.draw(N_INGEST)
    eng = BatchedSearchEngine(index, engine="codes", **common)
    try:
        t0 = time.perf_counter()
        first = eng.add_documents(added)
        t_add = time.perf_counter() - t0
        assert first == N_CHIP, first
        dead = np.unique(gold[:, 0])
        t0 = time.perf_counter()
        eng.delete(dead)
        t_del = time.perf_counter() - t0
        index = eng.index
    finally:
        eng.close()
    all_docs = np.concatenate([docs, added])
    gold2 = exact_topk(all_docs, queries, K, dead=dead)
    log(f"ingest: {N_INGEST} docs in {t_add:.3f}s; delete: {len(dead)} "
        f"docs in {t_del:.3f}s; {index.n_ids} ids, "
        f"{index.n_tombstones} tombstones")
    log("serve (after ingest and delete):")
    serve_all(index, all_docs, gold2, dead, "ingested")
    log(f"  exact top-{K} hits among the ingested docs: "
        f"{int((gold2 >= N_CHIP).sum())}")
    log(f"memory: {memory_line(jax)}")


def four_chips(jax, clock):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.cluster import ClusterEngine
    from repro.core import (CombinedEncoder, IntervalEncoder,
                            RoundingEncoder, TrimFilter)
    from repro.dist.shard_index import ShardedVectorIndex
    from repro.launch.mesh import make_shard_mesh
    from repro.serve.engine import BatchedSearchEngine

    assert len(jax.devices()) == 4, f"need 4 chips, have {jax.devices()}"
    encoder = CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1))
    common = dict(batch_size=BATCH, k=K, page=PAGE, trim=TrimFilter(TRIM),
                  engine="codes")

    t0 = time.perf_counter()
    mix = Mixture(seed=0)
    docs = mix.draw(N_WIKI)
    queries = mix.draw(N_QUERIES)
    gold = exact_topk(docs, queries, K)
    log(f"data: {N_WIKI} docs x {N_FEATURES} features, {N_QUERIES} "
        f"queries, host float64 reference in "
        f"{time.perf_counter() - t0:.2f}s")

    # 4 doc shards: the rows go straight to their chips
    mesh = make_shard_mesh(4)
    c0, t0 = clock.total, time.perf_counter()
    rows = jax.device_put(docs, NamedSharding(mesh, P("data", None)))
    index = ShardedVectorIndex.build_sharded(rows, mesh, encoder=encoder)
    jax.block_until_ready(index)
    del rows
    total, per_chip = index_bytes(index)
    log(f"4 shards: build {time.perf_counter() - t0:.2f}s wall, "
        f"{clock.total - c0:.2f}s of it compiling; index {total} B, per "
        f"chip {per_chip}")
    # every chip holds its quarter of the sharded index (and its copy of
    # what is replicated), not only the coordinator
    assert len(per_chip) == 4 and min(per_chip.values()) >= total / 4, \
        per_chip
    eng = BatchedSearchEngine(index, **common)
    try:
        ids, scores, cold = serve(eng, queries)
        _, _, warm = serve(eng, queries)
    finally:
        eng.close()
    log(f"  4 shards: {N_QUERIES} queries, first pass {cold:.3f}s, warm "
        f"pass {warm:.3f}s")
    check_hits("4 shards", ids, scores, docs, queries, gold)
    log(f"memory after 4 shards: {memory_line(jax)}")
    del index, eng
    gc.collect()

    # 2 shards x 2 replica groups at the 1-chip size, one group failed
    docs = docs[:N_CHIP]
    gold = exact_topk(docs, queries, K)
    mesh = make_shard_mesh(2, 2)
    t0 = time.perf_counter()
    rows = jax.device_put(docs, NamedSharding(mesh, P("data", None)))
    index = ShardedVectorIndex.build_sharded(rows, mesh, encoder=encoder)
    jax.block_until_ready(index)
    del rows
    log(f"2 shards x 2 replicas: build {time.perf_counter() - t0:.2f}s, "
        f"index bytes per chip {index_bytes(index)[1]}")
    cluster = ClusterEngine(index, **common)
    try:
        streams = itertools.count()
        submit = lambda q: cluster.submit(q, stream=next(streams))
        ids, scores, healthy = serve(cluster, queries, submit)
        cluster.inject_failure(1)
        ids_f, scores_f, failed = serve(cluster, queries, submit)
        up = cluster.health.up_groups()
    finally:
        cluster.close()
    log(f"  cluster healthy pass {healthy:.3f}s; group 1 failed, pass "
        f"{failed:.3f}s on groups {up}")
    assert 1 not in up, up
    assert np.array_equal(ids, ids_f) and np.array_equal(scores, scores_f), \
        "failover results differ from the healthy cluster's"
    log("  failover results are bit-identical to the healthy cluster's")
    check_hits("cluster", ids, scores, docs, queries, gold)
    log(f"memory: {memory_line(jax)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the 4-chip phases only")
    args = ap.parse_args()
    try:
        from repro.launch.hostdev import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package: {e}",
              file=sys.stderr)
        return 2
    use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "run on another backend", file=sys.stderr)
        return 1
    log(f"devices: {len(jax.devices())} x {dev.device_kind}; jax "
        f"{jax.__version__}")
    clock = CompileClock(jax)
    (four_chips if args.four_chips else one_chip)(jax, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
