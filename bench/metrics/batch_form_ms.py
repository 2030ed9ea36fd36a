"""batch_form_ms: host ms per complete dispatch of the batcher forming its
batch, the span ``repro.engine.batch_form`` (``serve/engine.py``: dequeue,
queue-wait and occupancy records, stack and pad) that precedes each of the
window's complete dispatches, from the trace.  In a closed loop of full
batches it is most of the host gap between two dispatches."""

from bench.phases import span_ms


def read(run):
    if run.trace is None:
        return None
    return span_ms(run.trace, "repro.engine.batch_form", "before")
