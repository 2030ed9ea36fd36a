"""merge_rescore_ms: device time per dispatch on chip 0 of the programs that
run after phase 1 (``_merge_select`` / ``_merge_select_seg``, then
``_rescore``, of ``dist/shard_index.py``), from the trace: their runs in
the window's complete dispatches over the count of those dispatches."""

from bench.trace_reduce import per_dispatch_ms


def read(run):
    if run.trace is None:
        return None
    return per_dispatch_ms(run.trace, run.trace.chips[0],
                           ("jit__merge_select", "jit__rescore")) or None
