"""``bench/run.py`` refuses to run where it cannot measure: no TPU, or a
checkout that holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys

from bench import registry

ROOT = registry.ROOT
ARGS = ["--workload", "wiki1-closed", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = _run(ROOT, env)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "TPU" in res.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = _run(tmp_path, env)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
