"""Host ms per complete dispatch of the engine's and the index's phase
spans, from a trace reduction (``bench/trace_reduce.py``).

With an annotating tracer the engine's worker opens, per batch,
``repro.engine.wait`` (blocked until a batch can form),
``repro.engine.batch_form`` (dequeue, stack, pad), ``repro.engine.dispatch``
(holding the index's ``repro.search.encode``, ``repro.search.query_phase``
and ``repro.search.merge``, then ``repro.engine.readback``) and
``repro.engine.resolve`` (trace spans, futures), on one thread.  A span
belongs to the dispatch it lies in (``"in"``), to the next dispatch to
start after it (``"before"``: it prepared that dispatch), or to the last
dispatch that ended before it (``"after"``: it finished that one).
"""

from __future__ import annotations

import bisect
from typing import Optional

from bench.trace_reduce import DISPATCH_SPAN


def span_ms(trace, name: str, side: str) -> Optional[float]:
    """Host ms per complete dispatch of the spans named ``name`` that
    belong to a complete dispatch; None when the window holds no complete
    dispatch or no such span (a program without the span)."""
    if side not in ("in", "before", "after"):
        raise ValueError(f"side must be in, before or after: {side!r}")
    if not trace.dispatches:
        return None
    # one worker thread: dispatches do not overlap, so they sort by start
    # and by end alike
    every = sorted((s, e) for n, s, e in trace.host_spans
                   if n == DISPATCH_SPAN)
    starts = [s for s, _ in every]
    ends = [e for _, e in every]
    complete = set(trace.dispatches)
    total, found = 0.0, False
    for n, s, e in trace.host_spans:
        if n != name:
            continue
        if side == "in":
            i = bisect.bisect_right(starts, s) - 1
            owner = every[i] if i >= 0 and e <= every[i][1] else None
        elif side == "before":
            i = bisect.bisect_left(starts, e)
            owner = every[i] if i < len(every) else None
        else:
            i = bisect.bisect_right(ends, s) - 1
            owner = every[i] if i >= 0 else None
        if owner in complete:
            total += e - s
            found = True
    return total / len(trace.dispatches) * 1e3 if found else None
