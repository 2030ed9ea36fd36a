"""Process set-up for entry points -- jax-free on purpose.

``XLA_FLAGS=--xla_force_host_platform_device_count=N`` only takes effect
when set before the first jax import, so entry points call
:func:`force_host_devices` at the very top of the module, ahead of any
repro/jax import.  :func:`use_compile_cache` points JAX's persistent
compilation cache at one fixed directory, so a second run of the same
programs skips their compiles.  The CLI front-ends (repro.launch.serve,
chip_smoke.py, the benchmarks) share this one copy.
"""

from __future__ import annotations

import os
import sys

__all__ = ["force_host_devices", "peek_int_arg", "use_compile_cache"]

_FLAG = "xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout root: src/repro/launch/hostdev.py -> three levels up
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def force_host_devices(n: int) -> None:
    """Request ``n`` virtual host devices; no-op for n <= 1 or when the
    flag is already present (an explicit user setting wins)."""
    if n > 1 and _FLAG not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --{_FLAG}={n}").strip()


def peek_int_arg(argv, name: str) -> int:
    """Pre-argparse peek at an int option (``--opt N`` or ``--opt=N``);
    malformed or absent -> 0, leaving the error to argparse."""
    for i, a in enumerate(argv):
        try:
            if a == name:
                return int(argv[i + 1])
            if a.startswith(name + "="):
                return int(a.split("=", 1)[1])
        except (IndexError, ValueError):
            return 0
    return 0


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` already in the environment wins and
    nothing is set.  Otherwise the cache lives at ``<checkout>/.jax_cache``
    (gitignored): a fixed path, because the path is part of the cache key.
    The choice goes into the environment, so child processes share the
    cache, and into jax's config when jax is already imported."""
    path = os.environ.get(_CACHE_ENV)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ[_CACHE_ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
