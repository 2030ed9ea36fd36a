"""search_p95_ms: 95th percentile latency of every request sent in the
window (host clock)."""

from bench.stats import percentile_ms


def read(run):
    return percentile_ms(run.latencies_s, 95) if len(run.latencies_s) else None
