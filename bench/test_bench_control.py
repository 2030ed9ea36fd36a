"""The correctness comparison on the CPU at a small size: a sound run passes,
and the controls and each planted fault of the timed path come out not
correct.  These drive the harness as ``bench/run.py`` does, without its
look for a chip."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import check, controls, harness, registry

ROOT = registry.ROOT


def small_spec(n_shards=1, n_docs=8192):
    """``wiki1-closed`` cut to CPU size, its docs split over ``n_shards``
    doc shards, one per device."""
    spec = registry.resolve("wiki1-closed", registry.load_benchmark())
    spec["cell"] = dict(spec["cell"], chips=n_shards)
    spec["config"] = dict(spec["config"], n_docs=n_docs, batch_size=16,
                          page=32, n_shards=n_shards, chips=n_shards)
    spec["traffic"] = dict(spec["traffic"], clients=32, pool=256, sample=32,
                           warmup_batches=1)
    return spec


def run(spec, seed, **kw):
    return harness.run_cell(spec, seed, 1.0, False,
                            t_start=time.perf_counter(), name="test", **kw)


@pytest.fixture(scope="module")
def sound():
    spec = small_spec()
    on_program, on_reference = controls.control_hooks(spec["config"])
    return spec, run(spec, 2**31 + 11, on_program=on_program,
                     on_reference=on_reference)


def test_sound_run_is_correct_and_its_line_has_the_keys(sound):
    spec, out = sound
    assert out["correct"] is True
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "search_qps",
                                   "search_p50_ms", "search_p95_ms"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name
    json.dumps(out)


@pytest.mark.parametrize("kind", ["control_bf16", "control_int8"])
def test_controls_are_not_correct(sound, kind):
    spec, out = sound
    ok, checks = check.verdict(out["readings"][kind],
                               spec["config"]["limits"])
    assert not ok, checks


class Faulty:
    """The served index with a fault planted where answers are made."""

    def __init__(self, index, fault):
        self.index, self.fault, self.last = index, fault, None

    def search(self, queries, **kw):
        q = np.asarray(queries)
        if self.fault == "half":
            # half of the batch left out, the rest answered twice
            h = len(q) // 2
            ids, sc = map(np.asarray, self.index.search(q[:h], **kw))
            return (np.concatenate([ids, ids])[:len(q)],
                    np.concatenate([sc, sc])[:len(q)])
        ids, sc = map(np.asarray, self.index.search(queries, **kw))
        if self.fault == "stale":
            # a search that hands back its previous answers unchanged
            prev, self.last = self.last, (ids, sc)
            return prev if prev is not None else (ids, sc)
        if self.fault == "altered":
            # one hit of every answer replaced where it is produced
            ids = ids.copy()
            ids[:, 0] = (ids[:, 0] + 1) % self.index.n_docs
            return ids, sc
        raise ValueError(self.fault)


@pytest.mark.parametrize("fault", ["stale", "half", "altered"])
def test_planted_faults_are_not_correct(fault):
    out = run(small_spec(), 2**31 + 12,
              fault=lambda index: Faulty(index, fault))
    assert out["correct"] is False, out["checks"]


_FOUR = r"""
import json, sys, time, dataclasses
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import harness
from bench.test_bench_control import small_spec

def exchange_left_out(index):
    # the coordinator merges its own shard's page alone: every other
    # shard's candidates never reach it
    live = index.live.at[1:].set(False)
    return dataclasses.replace(index, live=live)

spec = small_spec(4, n_docs=4 * 4096)
out = {}
for name, fault in (("sound", None), ("exchange", exchange_left_out)):
    r = harness.run_cell(spec, 2**31 + 13, 1.0, False,
                         t_start=time.perf_counter(), name="test4",
                         fault=fault)
    out[name] = [r["correct"], r["checks"]]
print(json.dumps(out))
"""


def test_four_shards_sound_and_exchange_left_out():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    res = subprocess.run([sys.executable, "-c", _FOUR, ROOT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["sound"][0] is True, out["sound"][1]
    assert out["exchange"][0] is False, out["exchange"][1]
