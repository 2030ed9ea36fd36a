"""The ``msmarco1-closed`` cell on the CPU at a small size: its configuration
resolves and sizes its work, the blocked reference it names equals
``token_match``, and the fused path at 768 features (1,536 token columns)
agrees with the single-device index and comes out correct against the
reference on 1 and 4 devices, while both controls do not."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import check, controls, corpus, registry, work

ROOT = registry.ROOT
CELL = "msmarco1-closed"


def small_spec(n_shards=1, n_docs=8192):
    """``msmarco1-closed`` cut to CPU size, every width kept, its docs
    split over ``n_shards`` doc shards, one per device."""
    spec = registry.resolve(CELL, registry.load_benchmark())
    spec["cell"] = dict(spec["cell"], chips=n_shards)
    spec["config"] = dict(spec["config"], n_docs=n_docs, batch_size=16,
                          page=32, n_shards=n_shards, chips=n_shards)
    spec["traffic"] = dict(spec["traffic"], clients=32, pool=256, sample=32,
                           warmup_batches=1)
    return spec


def test_cell_resolves_and_sizes_its_work():
    spec = registry.resolve(CELL, registry.load_benchmark())
    cfg, traffic = spec["config"], spec["traffic"]
    assert spec["cell"]["config"] == cfg["name"] == "msmarco768-1chip"
    assert cfg["n_docs"] == 8_842_240 // 8 == 1_105_280
    assert (cfg["n_features"], cfg["n_shards"], cfg["chips"]) == (768, 1, 1)
    assert traffic["clients"] == 2 * cfg["batch_size"] == 128
    assert work.code_columns(cfg) == 1536
    # 1,105,280 docs x 1,536 int8 tokens + a live byte per doc, 64
    # queries x 1,536 tokens (int8 token + f32 weight), 64 x 320 pairs
    assert work.phase1_bytes(cfg) == 1_699_470_720
    assert hasattr(registry.reference(cfg["reference"]), "Reference")


@pytest.mark.parametrize("name", ["msmarco768-1chip", "wiki-1chip"])
@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_blocked_reference_equals_token_match(name, precision):
    """Tokens, histogram, envelope and answers of the blocked reference are
    ``token_match``'s, bit for bit, over more rows than one block step."""
    blocked = registry.reference("token_match_blocked")
    plain = registry.reference("token_match")
    assert blocked.BLOCK_ROWS < 9472
    cfg = dict(registry.config(name), n_docs=9472, page=32)
    seed = 2**31 + 21
    mix = cfg["corpus"]
    queries = corpus.query_pool(seed, 24, cfg["n_features"],
                                mix["n_topics"], mix["noise"])
    a = plain.Reference(cfg, seed, precision)
    b = blocked.Reference(cfg, seed, precision)
    assert np.array_equal(np.asarray(a.codes), np.asarray(b.codes))
    assert np.array_equal(np.asarray(a.hist), np.asarray(b.hist))
    for x, y in zip(a.envelope(queries) + a.answer(queries),
                    b.envelope(queries) + b.answer(queries)):
        assert np.array_equal(x, y)


def run(spec, seed, **kw):
    """One run of the cut cell; the window holds a few dispatches even on
    a loaded CPU, where one dispatch over 1,536 columns takes ~0.5 s."""
    from bench import harness

    return harness.run_cell(spec, seed, 3.0, False,
                            t_start=time.perf_counter(), name="test", **kw)


def test_sound_run_is_correct_and_controls_are_not():
    spec = small_spec()
    on_program, on_reference = controls.control_hooks(spec["config"])
    out = run(spec, 2**31 + 22, on_program=on_program,
              on_reference=on_reference)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    for kind in ("control_bf16", "control_int8"):
        ok, checks = check.verdict(out["readings"][kind],
                                   spec["config"]["limits"])
        assert not ok, (kind, checks)


_PARITY = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import numpy as np
from bench import harness
from bench.test_bench_msmarco import small_spec
from repro.core import (CombinedEncoder, IntervalEncoder, RoundingEncoder,
                        TrimFilter, VectorIndex)
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh

n_shards = int(sys.argv[2])
rng = np.random.default_rng(23)
V = rng.normal(size=(203, 768)).astype(np.float32)
Q = rng.normal(size=(6, 768)).astype(np.float32)
enc = CombinedEncoder(RoundingEncoder(1), IntervalEncoder(0.1))
single = VectorIndex.build(V, encoder=enc)
sidx = ShardedVectorIndex.build_sharded(V, make_shard_mesh(n_shards),
                                        encoder=enc)
same = not sidx.has_postings
# a short page is the same candidate set only on one shard
cases = [(2 * len(V), None), (2 * len(V), TrimFilter(0.05))]
cases += [(24, TrimFilter(0.05))] if n_shards == 1 else []
for page, trim in cases:
    kw = dict(k=5, page=page, trim=trim, engine="fused")
    i1, s1 = single.search(Q, **kw)
    i2, s2 = sidx.search(Q, **kw)
    same = same and np.array_equal(np.asarray(i1), np.asarray(i2))
    same = same and np.array_equal(np.asarray(s1), np.asarray(s2))
same = same and not sidx.has_postings
spec = small_spec(n_shards, n_docs=n_shards * 2048)
r = harness.run_cell(spec, 2**31 + 24, 3.0, False,
                     t_start=time.perf_counter(), name="test768")
print(json.dumps({"parity": bool(same), "correct": r["correct"],
                  "checks": r["checks"]}))
"""


@pytest.mark.parametrize("n_shards", [1, 4])
def test_fused_768_matches_single_index_and_reference(n_shards):
    """1,536 token columns on 1 and 4 (virtual) devices: the fused sharded
    search equals ``VectorIndex.search`` bit for bit at a full page, with
    and without trim (and at a short page on one shard), builds no posting
    lists, and a run of the cell is correct against the reference's
    envelope."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_shards}",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    res = subprocess.run([sys.executable, "-c", _PARITY, ROOT,
                          str(n_shards)], env=env, capture_output=True,
                         text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["parity"] is True
    assert out["correct"] is True, out["checks"]
