"""Observability v3 (repro/obs): device byte accounting, ES
_cluster/health, diagnostics bundles, exposition hardening, the host-seam
lint, and the perf-regression gate.

The pinned invariants:

* **byte accounting is exact** -- ``device_bytes()`` totals equal the
  sum of unique leaf ``nbytes`` (shape x dtype, never measured) for
  flat, sharded, segmented and quantized indexes; aliased leaves count
  once; totals SHRINK after ``compact()``; on a replicated mesh the
  per-device attribution exceeds the logical total by exactly the
  replication factor;
* **health reconciles** -- ``cluster_health()`` walks green -> yellow
  -> red -> green exactly as failures are injected, and its transition
  ledger matches the health counters one-for-one;
* **the bundle is complete** -- ``diagnostics_bundle()`` contains every
  documented section and survives a JSON round trip;
* **exposition always parses** -- metric names are sanitized, label
  values escaped, comma-bearing label identities kept lossless;
* **the lint lints** -- ``tools/check_host_seams.py`` passes the repo
  and fails a seeded host call inside a jitted body;
* **the gate gates** -- ``benchmarks.check`` flags a halved headline,
  a busted overhead bar, and an inverted kernel-byte claim, and SKIPs
  (never silently passes) single-run artifacts.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.cluster import ClusterEngine
from repro.core import VectorIndex
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh
from repro.obs import (BUNDLE_SECTIONS, CompileWatch, MetricsRegistry,
                       cluster_health, device_bytes, device_gauges,
                       diagnostics_bundle, format_device_line,
                       format_health_line, health_gauges, node_stats,
                       prometheus_text, resident_leaf_entries,
                       write_diagnostics)
from repro.serve.engine import BatchedSearchEngine

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_DOCS, N_FEAT = 60, 16


@pytest.fixture(scope="module")
def sidx():
    """Sharded index with an appended generation and tombstones, so the
    accounting sees the full segment lifecycle."""
    rng = np.random.default_rng(0)
    idx = ShardedVectorIndex.build_sharded(
        rng.normal(size=(N_DOCS, N_FEAT)).astype(np.float32),
        make_shard_mesh(1), seal_threshold=16)
    idx = idx.add_documents(
        rng.normal(size=(24, N_FEAT)).astype(np.float32))
    return idx.delete(np.array([3, N_DOCS + 2]))


@pytest.fixture()
def queries():
    return np.random.default_rng(1).normal(
        size=(6, N_FEAT)).astype(np.float32)


def _leaf_total(index) -> int:
    """Reference total: sum of unique leaf nbytes, straight off the
    leaf iterator the accounting itself consumes."""
    seen = {}
    for _path, _section, arr in resident_leaf_entries(index):
        if arr is not None and hasattr(arr, "nbytes"):
            seen[id(arr)] = arr
    return sum(int(a.nbytes) for a in seen.values())


# ------------------------------------------------------------ byte totals
def test_device_bytes_flat_index():
    idx = VectorIndex.build(np.random.default_rng(2).normal(
        size=(N_DOCS, N_FEAT)).astype(np.float32))
    dev = device_bytes(idx)
    assert dev["total_bytes"] == _leaf_total(idx) > 0
    assert dev["total_bytes"] == sum(l["nbytes"] for l in dev["leaves"])
    assert dev["total_bytes"] == sum(dev["sections"].values())
    assert dev["n_leaves"] == len(dev["leaves"])
    line = format_device_line(dev)
    assert "device_bytes total=" in line and "leaves=" in line


def test_device_bytes_sharded_segmented(sidx):
    dev = device_bytes(sidx)
    assert dev["total_bytes"] == _leaf_total(sidx) > 0
    assert dev["total_bytes"] == sum(l["nbytes"] for l in dev["leaves"])
    # the module fixture sealed one generation: base AND segments present
    assert dev["sections"]["base"] > 0
    assert dev["sections"]["segments"] > 0
    for leaf in dev["leaves"]:       # drained active buffers may be empty
        assert leaf["nbytes"] >= 0 and leaf["dtype"] != "?", leaf
    # every accounted leaf is a live device array (reconciliation)
    rec = dev["reconciliation"]
    assert rec["live_leaves"] == dev["n_leaves"]
    assert rec["accounted_bytes"] == dev["total_bytes"]
    assert rec["process_live_bytes"] >= dev["total_bytes"]


def test_device_bytes_quant_tables_counted(sidx, queries):
    before = device_bytes(sidx, reconcile=False)
    assert "quant" not in before["sections"]
    # int8 scoring lazily derives the quant tables; the ledger must see
    # them even though they are not pytree children
    sidx.search(queries, k=5, page=N_DOCS, engine="fused_int8")
    after = device_bytes(sidx, reconcile=False)
    assert after["sections"].get("quant", 0) > 0
    grown = after["total_bytes"] - before["total_bytes"]
    assert grown == after["sections"]["quant"]
    assert after["total_bytes"] == _leaf_total(sidx)


def test_device_bytes_shrink_after_compact():
    rng = np.random.default_rng(3)
    idx = ShardedVectorIndex.build_sharded(
        rng.normal(size=(64, N_FEAT)).astype(np.float32),
        make_shard_mesh(1), seal_threshold=16)
    idx = idx.add_documents(rng.normal(size=(32, N_FEAT)).astype(np.float32))
    idx = idx.delete(np.arange(40))
    before = device_bytes(idx, reconcile=False)["total_bytes"]
    compacted = idx.compact()
    after = device_bytes(compacted, reconcile=False)["total_bytes"]
    assert after < before, (after, before)
    assert after == _leaf_total(compacted)


def test_device_bytes_replicated_mesh_per_device():
    """On a 4 shard x 2 replica mesh every leaf is resident on 8 devices
    with 2x physical replication: per-device attribution must sum to
    exactly twice the logical total."""
    _run_subprocess(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh
from repro.obs import device_bytes, resident_leaf_entries

rng = np.random.default_rng(0)
idx = ShardedVectorIndex.build_sharded(
    rng.normal(size=(64, 16)).astype(np.float32), make_shard_mesh(4, 2))
dev = device_bytes(idx)
seen = {}
for _p, _s, arr in resident_leaf_entries(idx):
    if arr is not None and hasattr(arr, "nbytes"):
        seen[id(arr)] = arr
want = sum(int(a.nbytes) for a in seen.values())
assert dev["total_bytes"] == want, (dev["total_bytes"], want)
assert len(dev["per_device"]) == 8, dev["per_device"]
resident = sum(dev["per_device"].values())
assert resident == 2 * dev["total_bytes"], (resident, dev["total_bytes"])
assert dev["reconciliation"]["device_resident_bytes"] == resident
print("OK")
""")


def _run_subprocess(script: str) -> None:
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=_REPO)
    assert "OK" in out.stdout, out.stdout + out.stderr


# ----------------------------------------------------------- cluster health
def test_cluster_health_transitions_reconcile(sidx, queries):
    reg = MetricsRegistry()
    cl = ClusterEngine([sidx, sidx], batch_size=4, k=5, page=N_DOCS,
                       trim=None, engine="codes", metrics=reg)
    try:
        h = cl.cluster_health()
        assert h["status"] == "green"
        assert h["up_groups"] == h["n_groups"] == 2
        assert h["transitions"] == [] and h["pending_requests"] == 0
        assert "2/2up" in format_health_line(h)

        cl.mark_down(0)
        h = cl.cluster_health()
        assert h["status"] == "yellow" and list(h["down"]) == [0]
        cl.mark_down(1)
        h = cl.cluster_health()
        assert h["status"] == "red" and h["up_groups"] == 0

        cl.mark_up(0)
        cl.mark_up(1)
        h = cl.cluster_health()
        assert h["status"] == "green"
        # ledger vs counters: one-for-one
        events = [e["event"] for e in h["transitions"]]
        assert events.count("down") == 2
        assert events.count("up") == 2
        assert h["counters"]["down_transitions"] == 2
        assert h["counters"]["mark_ups"] == 2
        # every entry carries the generation that produced it, ordered
        gens = [e["generation"] for e in h["transitions"]]
        assert gens == sorted(gens)
        assert gens[-1] == h["generation"]
        # and the cluster still serves after the walk
        futs = [cl.submit(v, stream=i) for i, v in enumerate(queries)]
        assert all(f.result(timeout=60) for f in futs)
    finally:
        cl.close()


def test_node_stats_covers_every_device(sidx):
    import jax

    eng = BatchedSearchEngine(sidx, batch_size=4, k=5, page=N_DOCS,
                              trim=None, engine="codes")
    try:
        ns = node_stats(eng)
        assert ns["n_devices"] == len(jax.devices())
        assert set(ns["nodes"]) == {str(d) for d in jax.devices()}
        assert ns["total_index_bytes"] == \
            device_bytes(sidx, reconcile=False)["total_bytes"]
        assert ns["device_resident_bytes"] == \
            sum(n["index_bytes"] for n in ns["nodes"].values())
        for node in ns["nodes"].values():
            assert node["platform"] == jax.devices()[0].platform
    finally:
        eng.close()


# ------------------------------------------------------- diagnostics bundle
def test_diagnostics_bundle_sections_roundtrip(sidx, queries, tmp_path):
    from repro.obs import MetricsExporter, SlowLog, Tracer

    reg = MetricsRegistry()
    eng = BatchedSearchEngine(sidx, batch_size=4, k=5, page=N_DOCS,
                              trim=None, engine="codes", metrics=reg,
                              tracer=Tracer(sample=1.0),
                              slowlog=SlowLog(threshold_s=0.0, metrics=reg),
                              compile_watch=CompileWatch(metrics=reg))
    exporter = MetricsExporter(reg)
    try:
        for v in queries:
            eng.search(v, timeout=60)
        exporter.collect()
        bundle = diagnostics_bundle(eng, exporter=exporter, reason="test")
        assert set(BUNDLE_SECTIONS) <= set(bundle)
        assert bundle["meta"]["reason"] == "test"
        assert bundle["stats"]["requests"]["completed"] == len(queries)
        assert bundle["device"]["0"]["total_bytes"] > 0
        assert bundle["slowlog"]["stats"]["captured"] == len(queries)
        assert bundle["metrics_history"], "exporter history missing"
        path = write_diagnostics(eng, str(tmp_path), exporter=exporter,
                                 reason="unit test!")
        assert os.path.dirname(path) == str(tmp_path)
        with open(path) as f:
            loaded = json.load(f)          # survives a JSON round trip
        assert set(BUNDLE_SECTIONS) <= set(loaded)
        assert loaded["meta"]["reason"] == "unit test!"
    finally:
        eng.close()


def test_diagnostics_bundle_cluster_and_unwired_sections(sidx):
    """A bare cluster engine: every section key still present (None or
    empty where the plane is unwired), device table keyed per group."""
    cl = ClusterEngine([sidx, sidx], batch_size=4, k=5, page=N_DOCS,
                       trim=None, engine="codes")
    try:
        bundle = diagnostics_bundle(cl)
        assert set(BUNDLE_SECTIONS) <= set(bundle)
        assert set(bundle["device"]) == {"0", "1"}
        assert bundle["health"]["status"] == "green"
        json.dumps(bundle)                  # no unserializable leaves
    finally:
        cl.close()


# ------------------------------------------------------ exposition hardening
def test_prometheus_name_and_label_sanitization():
    text = prometheus_text({
        "counters": {"weird-metric.9x total": {"q=hi": 3}},
        "gauges": {"9lead": {"bad-key!=v": 1.5}},
    })
    assert "repro_weird_metric_9x_total_total" in text
    assert "repro__9lead" in text
    assert 'bad_key_="v"' in text
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        assert all(c.isalnum() or c in "_:" for c in name), line


def test_prometheus_label_value_escaping_roundtrip():
    reg = MetricsRegistry()
    reg.counter("hits", path='a\\b"c\nd').inc(2)
    text = prometheus_text(reg.snapshot())
    line = [l for l in text.splitlines()
            if l.startswith("repro_hits_total{")][0]
    assert '\\\\' in line and '\\"' in line and "\\n" in line
    assert "\n" not in line                 # the raw newline never leaks
    assert line.endswith(" 2")


def test_prometheus_comma_in_label_value_lossless():
    text = prometheus_text(
        {"gauges": {"g": {"device=TFRT_CPU_0,TFRT_CPU_1": 7}}})
    assert 'device="TFRT_CPU_0,TFRT_CPU_1"' in text


def test_health_and_device_gauges(sidx):
    reg = MetricsRegistry()
    cl = ClusterEngine([sidx, sidx], batch_size=4, k=5, page=N_DOCS,
                       trim=None, engine="codes")
    try:
        health_gauges(reg, cl.cluster_health())
        assert reg.value("cluster.health.status") == 0       # green
        assert reg.value("cluster.health.up_groups") == 2
        dev = device_bytes(sidx, reconcile=False)
        device_gauges(reg, dev, group="0")
        assert reg.value("device.index_bytes", group="0") == \
            dev["total_bytes"]
        text = prometheus_text(reg.snapshot())
        assert "repro_cluster_health_status" in text
        assert "repro_device_index_section_bytes" in text
        cl.mark_down(0)
        health_gauges(reg, cl.cluster_health())
        assert reg.value("cluster.health.status") == 1       # yellow
    finally:
        cl.close()


# ------------------------------------------------------------ host-seam lint
_LINT = os.path.join(_REPO, "tools", "check_host_seams.py")


def test_host_seam_lint_repo_clean():
    out = subprocess.run([sys.executable, _LINT],
                         capture_output=True, text=True, cwd=_REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


def test_host_seam_lint_catches_violations(tmp_path):
    (tmp_path / "bad.py").write_text(
        "import time\n"
        "import jax\n"
        "from repro.obs import MetricsRegistry\n"
        "@jax.jit\n"
        "def scores(x):\n"
        "    t0 = time.monotonic()\n"
        "    return x * t0\n"
        "def host_side():\n"
        "    time.sleep(0)              # NOT jitted: allowed\n"
        "def traced(x):\n"
        "    MetricsRegistry\n"
        "    return x\n"
        "y = jax.jit(traced)\n")
    out = subprocess.run([sys.executable, _LINT, str(tmp_path)],
                         capture_output=True, text=True)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "time.monotonic" in out.stderr
    assert "MetricsRegistry" in out.stderr
    assert "host_side" not in out.stderr


# --------------------------------------------------------- regression gate
def _gate(tmp_path, files):
    sys.path.insert(0, _REPO)
    try:
        from benchmarks import check
    finally:
        sys.path.pop(0)
    for name, doc in files.items():
        with open(tmp_path / f"BENCH_{name}.json", "w") as f:
            json.dump(doc, f)
    return check.main(["--artifacts", str(tmp_path)])


def _runs(*rowsets):
    return {"bench": "x", "runs": [{"rows": rows} for rows in rowsets]}


def test_gate_skips_single_run_then_catches_regression(tmp_path, capsys):
    assert _gate(tmp_path, {"shard_scale": _runs([{"qps": 100.0}])}) == 0
    assert "SKIP" in capsys.readouterr().out
    assert _gate(tmp_path, {"shard_scale": _runs(
        [{"qps": 100.0}], [{"qps": 30.0}])}) == 1
    assert "REGRESSION" in capsys.readouterr().out
    assert _gate(tmp_path, {"shard_scale": _runs(
        [{"qps": 100.0}], [{"qps": 80.0}])}) == 0


def test_gate_overhead_bars(tmp_path, capsys):
    doc = _runs([{"config": "off", "qps": 100.0},
                 {"config": "overhead", "relative_overhead": 0.08}])
    assert _gate(tmp_path, {"obs_scale": doc}) == 1
    assert "relative_overhead" in capsys.readouterr().out
    doc = _runs([{"config": "off", "qps": 100.0},
                 {"config": "overhead", "relative_overhead": 0.01},
                 {"config": "overhead_full", "relative_overhead": 0.04}])
    assert _gate(tmp_path, {"obs_scale": doc}) == 0


def test_gate_kernel_claim(tmp_path, capsys):
    rows = [{"n_docs": 100, "variant": "composed", "hbm_bytes": 1000,
             "wall_s": 1.0},
            {"n_docs": 100, "variant": "fused", "hbm_bytes": 2000,
             "wall_s": 0.5}]
    assert _gate(tmp_path, {"kernel_scale": {"rows": rows}}) == 1
    assert "fused bytes >= composed" in capsys.readouterr().out
    rows = [{"n_docs": 100, "variant": "composed", "hbm_bytes": 2000,
             "wall_s": 1.0},
            {"n_docs": 100, "variant": "fused", "hbm_bytes": 1000,
             "wall_s": 0.5},
            {"n_docs": 100, "variant": "fused_int8", "hbm_bytes": 400,
             "wall_s": 0.4}]
    assert _gate(tmp_path, {"kernel_scale": {"rows": rows}}) == 0


def test_gate_on_committed_artifacts():
    """The gate must pass (or skip) on exactly what is committed --
    otherwise `make bench-check` is red at HEAD."""
    sys.path.insert(0, _REPO)
    try:
        from benchmarks import check
    finally:
        sys.path.pop(0)
    assert check.main([]) == 0
