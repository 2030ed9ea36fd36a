"""search_qps: requests answered without error in the window, over the
window's seconds (host clock)."""


def read(run):
    return run.window.ok_in_window / run.window.seconds
