"""phase1_roofline_pct: the phase-1 kernel's least time over its measured
time per dispatch.  The least time is the call's bytes (``bench/work.py``:
one pass over the shard's token table, the queries in, the page out) at
the chip's HBM peak (``bench/peaks.py``); its compare/select/add work runs
on the vector unit, which has no published peak, so only bytes bound it."""

from bench import peaks, work
from bench.trace_reduce import phase1_kernel_ms


def read(run):
    if run.trace is None:
        return None
    ms = phase1_kernel_ms(run.trace)
    if not ms:
        return None
    return work.phase1_least_s(run.cfg, peaks.peaks(run.device_kind)) * 1e3 / ms * 100.0
