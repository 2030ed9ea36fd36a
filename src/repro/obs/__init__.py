"""repro.obs -- cluster-wide metrics, per-query tracing, ES-style stats.

The monitoring half of the paper's pitch: riding a fulltext-engine
architecture is supposed to buy "robustness, stability, scalability and
monitoring", and PRs 1-5 delivered the first three (sharded + replicated
serving, failover, auto-compaction, durability) while remaining
completely blind at runtime.  This package is the missing observability
plane, threaded through every serving layer at the host-side seams only
-- instrumentation records timestamps *around* jitted program dispatch,
and inside a program only ``jax.named_scope`` names reach the device:
scopes are metadata only, so compiled programs and their bit-parity pins
are untouched.

Each piece against its Elasticsearch analogue:

* :mod:`repro.obs.metrics` -- the data behind ``GET _nodes/stats`` and
  ``_cat/thread_pool``: a thread-safe registry of labelled counters,
  gauges, and log-bucketed latency histograms (p50/p90/p99 +
  count/sum), one lock-op per record, globally switchable for the
  overhead-sensitive (``benchmarks/obs_overhead.py`` pins the cost
  < 3% of QPS).
* :mod:`repro.obs.tracing` -- the slow log + tasks API + profile API in
  one object: a sampled per-request :class:`~repro.obs.tracing.Trace`
  follows a query submit -> queue wait -> batch formation -> device
  dispatch, with spill / failover-resubmit / health-transition events
  attached where they happened; ring-buffer retention, dump-on-demand,
  and ``annotation``/``annotating``: ``jax.profiler.TraceAnnotation``
  spans around every phase of a dispatch, so host spans line up with
  captured device profiles (the span and scope names are listed in
  ``docs/OBSERVABILITY.md``).
* :mod:`repro.obs.stats` -- ``GET _stats`` / ``_cat``: one snapshot
  schema per layer (``BatchedSearchEngine.stats()`` =
  ``_cat/thread_pool`` for one replica group,
  ``ClusterEngine.stats()`` = ``_cluster/stats`` + ``_cat/shards``,
  ``Store.stats()`` = ``_stats/translog`` + commit metadata), with the
  counter-reconciliation contract the smoke run asserts: queries issued
  == sum of per-group completions; one injected failure == one down /
  readmit transition pair.

``launch/serve.py --stats-interval S`` prints one ``_cat``-style line
every S seconds and a full stats + trace dump at exit; ``make
smoke-obs`` runs it on a 4-device cluster with an injected failure and
asserts the counters reconcile.

v2 adds the *why* layer (see ``docs/OBSERVABILITY.md`` for the full
ES mapping):

* :mod:`repro.obs.profile` -- ``_search?profile=true``: a per-query
  :class:`~repro.obs.profile.ProfileNode` phase tree (queue wait ->
  batch form -> encode -> phase-1 -> merge select -> rescore, with
  per-replica-group / per-generation candidate counts and the kernel
  path taken), via ``engine.search(..., profile=True)`` and
  ``ClusterEngine.profile(query)``.
* :mod:`repro.obs.slowlog` -- the search slow log with tail-based
  capture: every request gets a span skeleton; crossing
  ``slow_threshold_s`` (or erroring) promotes it to a full trace +
  profile tree at 100% capture, regardless of head sampling.
* :mod:`repro.obs.compile_watch` -- recompile telemetry: compiles
  counted per (wrapped entry point, abstract-shape signature), compile
  wall-time histogram, and a steady-state guard behind
  ``serve.py --fail-on-recompile``.
* :mod:`repro.obs.export` -- Prometheus text exposition of the
  registry + a JSONL snapshot history ring
  (``serve.py --metrics-file``).

v3 adds the *device* side -- what the arrays and the cluster hold:

* :mod:`repro.obs.device` -- exact index-resident byte accounting per
  shard/segment/quant-table leaf, per section and per device, reconciled
  against ``jax.live_arrays()`` (ES ``_nodes/stats`` store bytes +
  ``_cat/segments``).
* ``cluster_health()`` / ``node_stats()`` in :mod:`repro.obs.stats` --
  ES ``_cluster/health`` (green/yellow/red reconciled exactly against
  the HealthMap transition ledger) and ``_nodes/stats``.
* :mod:`repro.obs.diagnostics` -- the one-call support-diagnostics
  bundle (``serve.py --diagnostics-on-exit``, auto-dumped on failover
  and ``--kill-and-recover``).
"""

from .compile_watch import CompileWatch, active_watch, watch_region
from .device import (device_bytes, format_device_line,
                     resident_leaf_entries)
from .diagnostics import (BUNDLE_SECTIONS, diagnostics_bundle,
                          write_diagnostics)
from .export import (MetricsExporter, device_gauges, health_gauges,
                     prometheus_text)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      default_registry)
from .profile import ProfileNode, format_profile_tree, profile_from_trace
from .slowlog import SlowLog, start_request_trace
from .stats import (cluster_health, cluster_stats, engine_stats,
                    format_health_line, format_segments_line,
                    format_stats_line, index_stats, node_stats,
                    store_stats)
from .tracing import NULL_TRACE, Span, Trace, Tracer, annotating, annotation

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_registry",
    "Span", "Trace", "Tracer", "NULL_TRACE", "annotation", "annotating",
    "index_stats", "engine_stats", "cluster_stats", "store_stats",
    "cluster_health", "node_stats",
    "format_stats_line", "format_segments_line", "format_health_line",
    "ProfileNode", "format_profile_tree", "profile_from_trace",
    "SlowLog", "start_request_trace",
    "CompileWatch", "active_watch", "watch_region",
    "MetricsExporter", "prometheus_text", "health_gauges", "device_gauges",
    "device_bytes", "format_device_line", "resident_leaf_entries",
    "BUNDLE_SECTIONS", "diagnostics_bundle", "write_diagnostics",
]
