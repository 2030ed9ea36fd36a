"""Find a cell's configuration, traffic mix and its generator, metric
readers and reference by the names ``BENCHMARK.json`` and the files give
them.

A later cell, mix, metric or configuration is one new file and one new
entry; nothing here changes for it.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def prepare_env() -> None:
    """JAX's persistent compilation cache in the checkout, whatever the
    environment says (a fixed path: the path is part of the cache key), and
    every program cached however fast it compiled; the TPU runtime's own
    log files off (it would write them under ``/tmp``).  Call before jax is
    imported; if it already is, its config is set too."""
    settings = {"JAX_COMPILATION_CACHE_DIR": CACHE_DIR,
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
                "TPU_LOG_DIR": "disabled"}
    os.environ.update(settings)
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _checked(name: str) -> str:
    if not _NAME.match(name or ""):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_benchmark(path: str = os.path.join(ROOT, "BENCHMARK.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def _json(base: str, kind: str, name: str) -> dict:
    with open(os.path.join(base, kind, _checked(name) + ".json")) as f:
        return json.load(f)


def config(name: str, base: str = HERE) -> dict:
    return _json(base, "configs", name)


def traffic(name: str, base: str = HERE) -> dict:
    return _json(base, "traffic", name)


def _module(base: str, kind: str, name: str):
    path = os.path.join(base, kind, _checked(name) + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, base: str = HERE):
    """The ``read(run) -> float | None`` of ``metrics/<name>.py``."""
    return _module(base, "metrics", name).read


def generator(kind: str, base: str = HERE):
    """The ``drive(gen, seconds, wait_s, on_open)`` of
    ``generators/<kind>.py``."""
    return _module(base, "generators", kind).drive


def reference(name: str, base: str = HERE):
    """The module ``refs/<name>.py``."""
    return _module(base, "refs", name)


def cell_metrics(bench: dict, cell: str, group: str) -> List[dict]:
    """The entries of ``bench[group]`` (``end_to_end`` or ``per_layer``)
    that the cell reports: those without ``workloads``, and those that
    list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def resolve(workload: str, bench: dict, base: str = HERE) -> Dict:
    """-> {cell, config, traffic, end_to_end, per_layer} for a cell."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    return {
        "cell": cell,
        "config": config(cell["config"], base),
        "traffic": traffic(cell["traffic"], base),
        "end_to_end": cell_metrics(bench, workload, "end_to_end"),
        "per_layer": cell_metrics(bench, workload, "per_layer"),
    }
