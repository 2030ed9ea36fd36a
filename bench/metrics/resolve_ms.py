"""resolve_ms: host ms per complete dispatch of the batcher finishing its
batch, the span ``repro.engine.resolve`` (``serve/engine.py``: the dispatch
histogram, trace spans, resolving the futures) that follows each of the
window's complete dispatches, from the trace."""

from bench.phases import span_ms


def read(run):
    if run.trace is None:
        return None
    return span_ms(run.trace, "repro.engine.resolve", "after")
