"""phase1_kernel_ms: device time per dispatch of the Pallas kernels (ops
whose custom-call target is ``tpu_custom_call``) inside the query-phase
program, mean over the cell's chips, from the trace: the kernels' time in
the window's complete dispatches over their count."""

from bench.trace_reduce import phase1_kernel_ms


def read(run):
    return None if run.trace is None else phase1_kernel_ms(run.trace)
