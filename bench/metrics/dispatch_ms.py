"""dispatch_ms: mean of the engine's ``engine.dispatch.latency_s`` histogram
over the window (``serve/engine.py``: host wall around ``index.search`` and
the readback of its answers, one sample per batch)."""


def read(run):
    if not run.dispatches:
        return None
    return run.dispatch_s / run.dispatches * 1e3
