"""encode_ms: host ms per complete dispatch of the index's encode, the span
``repro.search.encode`` (``dist/shard_index.py``: normalize, encode, the
feature and expand masks, padding and replica placement, as eager ops)
inside the window's complete dispatches, from the trace."""

from bench.phases import span_ms


def read(run):
    if run.trace is None:
        return None
    return span_ms(run.trace, "repro.search.encode", "in")
