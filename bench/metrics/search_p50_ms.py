"""search_p50_ms: median latency of every request sent in the window, from
its send (closed loop) or due time (open loop) to its reply (host clock)."""

from bench.stats import percentile_ms


def read(run):
    return percentile_ms(run.latencies_s, 50) if len(run.latencies_s) else None
