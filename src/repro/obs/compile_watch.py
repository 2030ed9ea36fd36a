"""Recompile telemetry: count and attribute XLA compiles per call site.

In a JAX serving stack the usual answer to *why did p99 just double* is
a silent recompile -- a new batch shape, a grown segment width, a
forgotten static argument -- and nothing in the metrics plane observed
it.  This module closes that gap with ES hot-threads-style attribution:

* every jitted entry point in the serving path is wrapped in a cheap
  :func:`watch_region` (a thread-local push/pop around the dispatch);
* one process-wide ``jax.monitoring`` listener receives the backend
  compile-duration event and attributes it to the innermost region
  active ON THE CALLING THREAD (JAX compiles synchronously inside the
  dispatching call, so the region on top of the stack is the culprit);
  compiles outside any region land in an ``<unattributed>`` bucket;
* a :class:`CompileWatch` counts compiles per (region, signature),
  records compile wall time into the ``compile.duration_s`` histogram,
  and -- after :meth:`~CompileWatch.mark_steady` -- treats any further
  region-attributed compile as a steady-state recompile:
  ``compiles_steady_state`` in stats, and a hard error from
  :meth:`~CompileWatch.check` (``serve.py --fail-on-recompile``).

The ``sig`` a region carries is the abstract-shape signature of the
dispatch (batch shape, dtype, engine, static config), so two compiles
under one region with different sigs read as "new shape reached the
jit cache" while a repeat sig reads as genuine cache churn.

Regions nest: an engine-level ``engine.dispatch`` region encloses the
index's finer ``search.query_phase``/``search.merge_select`` regions,
and attribution always goes to the innermost -- each compile is counted
exactly once.  ``<unattributed>`` compiles (host-side analytics, test
scaffolding) never count against the steady state: the watch guards the
serving paths that were wrapped, not the whole process.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional, Tuple

__all__ = ["CompileWatch", "active_watch", "watch_metrics", "watch_region"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_UNATTRIBUTED = "<unattributed>"

_TLS = threading.local()            # .stack: [(watch, region, sig), ...]
_install_lock = threading.Lock()
_installed = False
_default: "Optional[CompileWatch]" = None
_default_lock = threading.Lock()


def _on_event(event: str, duration: float, **kwargs) -> None:
    if event != _COMPILE_EVENT:
        return
    stack = getattr(_TLS, "stack", None)
    if stack:
        watch, region, sig = stack[-1]
    else:
        watch, region, sig = active_watch(), _UNATTRIBUTED, ()
    watch._record(region, sig, duration)


def _ensure_listener() -> None:
    """Register the (one, process-wide) monitoring listener.  JAX offers
    no per-listener unregister, so a single dispatcher routes events to
    whichever watch owns the active region."""
    global _installed
    if _installed:
        return
    with _install_lock:
        if _installed:
            return
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_event)
        except Exception:  # pragma: no cover - jax always present in-repo
            pass
        _installed = True


class _Region:
    __slots__ = ("watch", "name", "sig")

    def __init__(self, watch: "CompileWatch", name: str, sig: Tuple):
        self.watch, self.name, self.sig = watch, name, sig

    def __enter__(self):
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        stack.append((self.watch, self.name, self.sig))
        return self

    def __exit__(self, *exc):
        _TLS.stack.pop()
        return False


class CompileWatch:
    """Per-(region, signature) compile counters + steady-state guard.

    ``metrics`` (default: the process registry) receives
    ``compile.total`` / ``compile.steady_state`` counters and the
    ``compile.duration_s`` histogram, all labelled ``fn=<region>``, so
    ``stats()`` rollups and the Prometheus exporter see compiles next to
    the latencies they perturb.
    """

    def __init__(self, metrics=None, enabled: bool = True):
        from repro.obs.metrics import default_registry

        self.enabled = enabled
        self.metrics = metrics if metrics is not None else default_registry()
        self._lock = threading.Lock()
        self._counts: Dict[Tuple[str, Tuple], int] = {}
        self._steady = False
        self._steady_events: List[dict] = []
        self._total = 0
        self._steady_total = 0
        if enabled:
            _ensure_listener()

    # -------------------------------------------------------------- regions
    def region(self, name: str, sig=()):
        """Context manager attributing any compile inside to ``name``
        with abstract-shape signature ``sig`` (a small hashable tuple).
        Cost when nothing compiles: a thread-local append/pop."""
        if not self.enabled:
            return contextlib.nullcontext()
        return _Region(self, name, tuple(sig))

    # ------------------------------------------------------------ recording
    def _record(self, region: str, sig: Tuple, duration: float) -> None:
        with self._lock:
            key = (region, sig)
            repeat = key in self._counts
            self._counts[key] = self._counts.get(key, 0) + 1
            self._total += 1
            # steady-state violations are REGION compiles only: the watch
            # guards the wrapped serving paths, not unrelated host code
            steady = self._steady and region != _UNATTRIBUTED
            if steady:
                self._steady_total += 1
                self._steady_events.append({
                    "fn": region,
                    "sig": [str(s) for s in sig],
                    "duration_s": float(duration),
                    "repeat_sig": repeat,
                })
        self.metrics.histogram("compile.duration_s", fn=region).observe(
            duration)
        self.metrics.counter("compile.total", fn=region).inc()
        if steady:
            self.metrics.counter("compile.steady_state", fn=region).inc()

    # ----------------------------------------------------------- steadiness
    def mark_steady(self) -> None:
        """Declare warmup over: every region-attributed compile after
        this point is an unexpected steady-state recompile."""
        with self._lock:
            self._steady = True

    def check(self) -> None:
        """Raise ``RuntimeError`` listing every steady-state recompile
        (the ``--fail-on-recompile`` hard error); no-op when clean."""
        with self._lock:
            events = list(self._steady_events)
        if events:
            detail = "; ".join(
                f"{e['fn']}(sig={','.join(e['sig']) or '-'}"
                f"{', repeat' if e['repeat_sig'] else ''})"
                for e in events)
            raise RuntimeError(
                f"{len(events)} steady-state recompile(s): {detail}")

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._steady_events.clear()
            self._steady = False
            self._total = 0
            self._steady_total = 0

    # ---------------------------------------------------------------- stats
    @property
    def compiles_total(self) -> int:
        with self._lock:
            return self._total

    @property
    def compiles_steady_state(self) -> int:
        with self._lock:
            return self._steady_total

    def stats(self) -> dict:
        """The stats-section dict: totals, per-function compile counts,
        distinct signatures seen, and any steady-state events."""
        with self._lock:
            by_fn: Dict[str, int] = {}
            for (region, _sig), c in self._counts.items():
                by_fn[region] = by_fn.get(region, 0) + c
            return {
                "compiles_total": self._total,
                "compiles_steady_state": self._steady_total,
                "steady": self._steady,
                "signatures": len(self._counts),
                "by_function": by_fn,
                "steady_events": list(self._steady_events),
            }


def active_watch() -> CompileWatch:
    """The process-default watch (what engines and serve.py share when
    none is injected -- the :func:`repro.obs.metrics.default_registry`
    pattern)."""
    global _default
    if _default is None:
        w = CompileWatch()
        with _default_lock:
            if _default is None:
                _default = w
    return _default


def _thread_watch() -> CompileWatch:
    """The watch active on this thread, else the process default."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1][0] if stack else active_watch()


def watch_region(name: str, sig=()):
    """A region on whichever watch is already active on this thread
    (else the process default) -- how the index's inner jitted seams
    (``search.query_phase``, ``ingest.append``, ``merge.postings``)
    inherit the engine's watch without threading a reference through
    every call."""
    return _thread_watch().region(name, sig)


def watch_metrics():
    """The metrics registry :func:`watch_region`'s regions report to on
    this thread -- where the index counts its own events
    (``index.df_table.builds``)."""
    return _thread_watch().metrics
