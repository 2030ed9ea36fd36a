"""setup_s: process start to the window's first timed request (host clock):
corpus, index build, engine start, warm-up, and compiles or cache loads."""


def read(run):
    return run.setup_s
