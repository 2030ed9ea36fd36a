"""One-call support-diagnostics bundle (the ES diagnostics tarball).

When an ES cluster misbehaves, support asks for one artifact: the
diagnostics bundle -- every ``_stats``/``_cluster/health``/
``_nodes/stats`` surface plus recent logs, captured at one instant,
parseable offline.  :func:`diagnostics_bundle` is that artifact for this
stack: a single JSON document snapshotting every obs surface the repo
has grown --

========================  ==============================================
section                   contents (ES analogue)
========================  ==============================================
``meta``                  wall/monotonic timestamps, dump reason,
                          backend + device count
``stats``                 ``engine.stats()`` rollup (``_stats``)
``health``                :func:`~repro.obs.stats.cluster_health`
                          (``_cluster/health``; None for a single
                          engine -- no cluster state to report)
``nodes``                 :func:`~repro.obs.stats.node_stats`
                          (``_nodes/stats``)
``device``                per-group :func:`~repro.obs.device.
                          device_bytes` leaf tables (``_cat/segments``
                          bytes view)
``compile``               compile-watch counters + steady-state events
``slowlog``               the slow-log ring, NOT cleared (dumping
                          diagnostics must not eat the evidence)
``traces``                the tracer ring, when sampling is on
``metrics``               full registry snapshot
``metrics_history``       the exporter's recent collection ring, when
                          an exporter is polling
========================  ==============================================

Every section key is ALWAYS present (None/empty when the surface is not
wired), so consumers -- and ``make smoke-health`` -- can assert bundle
completeness structurally.  :func:`write_diagnostics` wraps it in a
timestamped file; ``serve.py --diagnostics-on-exit DIR`` dumps one at
exit and automatically on failover and ``--kill-and-recover``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

__all__ = ["diagnostics_bundle", "write_diagnostics", "BUNDLE_SECTIONS"]

BUNDLE_SECTIONS = ("meta", "stats", "health", "nodes", "device", "compile",
                   "slowlog", "traces", "metrics", "metrics_history")


def _jsonable(obj):
    """``json.dump`` default: numpy scalars/arrays and sets degrade to
    plain python; anything else degrades to ``repr`` rather than
    failing the bundle (a diagnostics dump must not raise over one
    exotic value)."""
    try:
        import numpy as np

        if isinstance(obj, np.ndarray):
            return obj.tolist()
        if isinstance(obj, np.generic):
            return obj.item()
    except Exception:
        pass
    if isinstance(obj, (set, frozenset)):
        return sorted(obj)
    return repr(obj)


def diagnostics_bundle(engine, *, exporter=None,
                       reason: Optional[str] = None) -> dict:
    """Snapshot every obs surface of ``engine`` (a
    ``BatchedSearchEngine`` or ``ClusterEngine``) into one JSON-ready
    dict with the :data:`BUNDLE_SECTIONS` keys.  ``exporter`` (a
    :class:`~repro.obs.export.MetricsExporter`) contributes its recent
    collection history when provided; ``reason`` records why the bundle
    was cut (``"exit"``, ``"failover"``, ``"kill-and-recover"``)."""
    from repro.obs.device import device_bytes
    from repro.obs.stats import cluster_health, node_stats

    meta = {
        "t_wall": time.time(),
        "t_monotonic": time.monotonic(),
        "reason": reason,
    }
    try:
        import jax

        meta["backend"] = jax.default_backend()
        meta["n_devices"] = jax.device_count()
    except Exception:
        pass

    batchers = getattr(engine, "batchers", None)
    if batchers is not None:
        health = cluster_health(engine)
        device = {str(g): device_bytes(b.index)
                  for g, b in enumerate(batchers)}
    else:
        health = None
        device = {"0": device_bytes(engine.index)}

    watch = getattr(engine, "compile_watch", None)
    slowlog = getattr(engine, "slowlog", None)
    tracer = getattr(engine, "tracer", None)

    return {
        "meta": meta,
        "stats": engine.stats(),
        "health": health,
        "nodes": node_stats(engine),
        "device": device,
        "compile": watch.stats() if watch is not None else None,
        "slowlog": (None if slowlog is None
                    else {"entries": slowlog.dump(clear=False),
                          "stats": slowlog.stats()}),
        "traces": (None if tracer is None
                   else {"entries": tracer.dump(),
                         "stats": tracer.stats()}),
        "metrics": engine.metrics.snapshot(),
        "metrics_history": (exporter.history()
                            if exporter is not None else []),
    }


def write_diagnostics(engine, directory: str, *, exporter=None,
                      reason: Optional[str] = None) -> str:
    """Cut a bundle and write it as ``diagnostics-<utc>-<reason>.json``
    under ``directory`` (created if needed); returns the file path.
    File names carry a monotonic disambiguator so two dumps in the same
    second (failover then exit) never clobber each other."""
    bundle = diagnostics_bundle(engine, exporter=exporter, reason=reason)
    os.makedirs(directory, exist_ok=True)
    stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
    tag = f"{time.monotonic_ns() % 1_000_000:06d}"
    path = os.path.join(
        directory,
        f"diagnostics-{stamp}-{tag}-{reason or 'manual'}.json")
    with open(path, "w") as f:
        json.dump(bundle, f, indent=1, default=_jsonable)
    return path
