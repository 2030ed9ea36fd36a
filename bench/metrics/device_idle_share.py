"""device_idle_share: 1 - (union of the chip's busy op intervals over the
traced window), as a percentage, mean over the cell's chips."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace
    return (1.0 - t.busy_s / t.window_s) * 100.0
