"""BENCHMARK.json against its files: every cell resolves by name, a new
configuration, mix or metric is found by adding a file, and the work and
peak tables hold."""

import json
import os
import re
import shutil

import pytest

from bench import peaks, registry, work

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return registry.load_benchmark()


def test_every_cell_resolves_to_its_files(bench):
    for cell in bench["workloads"]:
        spec = registry.resolve(cell["name"], bench)
        cfg = spec["config"]
        assert cfg["name"] == cell["config"]
        assert cfg["chips"] == cell["chips"]
        assert callable(registry.generator(spec["traffic"]["kind"]))
        for m in spec["end_to_end"] + spec["per_layer"]:
            assert callable(registry.reader(m["name"]))
        assert hasattr(registry.reference(cfg["reference"]), "Reference")
        assert {"failed", "unanswered", "rank_gap", "score_err"} <= set(
            cfg["limits"])
        names = [m["name"] for m in spec["end_to_end"]]
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"]


def test_config_entries_match_files(bench):
    for c in bench["configs"]:
        with open(os.path.join(registry.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["n_docs"] % (128 * cfg["n_shards"]) == 0


def test_names_and_keys_follow_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    assert len(layers) >= 4


def test_a_new_config_mix_and_metric_are_found_by_adding_files(tmp_path,
                                                               bench):
    base = tmp_path / "bench"
    shutil.copytree(registry.HERE, base,
                    ignore=shutil.ignore_patterns("__pycache__", "*.gz"))
    cfg = registry.config("wiki-1chip")
    cfg.update(name="wiki-half", n_docs=cfg["n_docs"] // 2)
    (base / "configs" / "wiki-half.json").write_text(json.dumps(cfg))
    (base / "traffic" / "closed64.json").write_text(json.dumps(
        {"kind": "paced", "clients": 64, "pool": 64, "sample": 8}))
    (base / "generators" / "paced.py").write_text(
        "def drive(gen, seconds, wait_s, on_open):\n    return 'paced'\n")
    (base / "metrics" / "dispatch_ms.closed64.py").write_text(
        "def read(run):\n    return 1.5\n")
    new = dict(bench)
    new["configs"] = bench["configs"] + [
        {"name": "wiki-half", "source": "https://arxiv.org/abs/1706.00957",
         "file": "bench/configs/wiki-half.json", "reduced": ["n_docs"],
         "why": "half a shard"}]
    new["workloads"] = bench["workloads"] + [
        {"name": "half-closed64", "config": "wiki-half",
         "traffic": "closed64", "chips": 1, "why": "half the clients"}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "dispatch_ms.closed64", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "batcher (serve/engine.py)",
         "moves": "search_p50_ms", "workloads": ["half-closed64"]}]
    spec = registry.resolve("half-closed64", new, base=str(base))
    assert spec["config"]["n_docs"] == cfg["n_docs"]
    assert spec["traffic"]["clients"] == 64
    drive = registry.generator(spec["traffic"]["kind"], str(base))
    assert drive(None, 1.0, 1.0, None) == "paced"
    assert "dispatch_ms.closed64" in [m["name"] for m in spec["per_layer"]]
    assert registry.reader("dispatch_ms.closed64", str(base))(None) == 1.5
    # the narrower metric stays out of the cells it does not list
    spec1 = registry.resolve("wiki1-closed", new, base=str(base))
    assert "dispatch_ms.closed64" not in [m["name"]
                                         for m in spec1["per_layer"]]


def test_unknown_names_are_refused(bench):
    with pytest.raises(KeyError):
        registry.resolve("no-such-cell", bench)
    with pytest.raises(ValueError):
        registry.config("../BENCHMARK")


def test_phase1_bytes_at_the_cell_shapes():
    one = registry.config("wiki-1chip")
    four = dict(one, n_docs=4 * one["n_docs"], n_shards=4)
    # per shard: 1,045,376 docs x 800 int8 tokens + a live byte per doc,
    # 128 queries x 800 tokens (int8 token + f32 weight), 128 x 320 pairs
    table = 1_045_376 * 800 + 1_045_376
    expect = table + 128 * 800 * 5 + 128 * 320 * 8
    assert work.phase1_bytes(one) == expect == 838_185_856
    assert work.phase1_bytes(four) == expect      # same shard per chip
    v5e = peaks.peaks("TPU v5 lite")
    assert work.phase1_least_s(one, v5e) == pytest.approx(expect / 819e9)


def test_peaks_table():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_s"] == 819e9
    assert v5e["bf16_flops"] == 197e12 and v5e["int8_ops"] == 393e12
    for kind in ("cpu", "TPU v4", "TPU v6 lite", ""):
        with pytest.raises(KeyError):
            peaks.peaks(kind)
