"""Closed loop: ``clients`` clients, each with one request in flight; a
client sends its next query as soon as its reply comes.  Latency runs from
the send to the reply.  The clients keep running from warm-up into the
window, so the window opens on a full queue."""

import queue
import time

from bench.loadgen import Run


def drive(gen, seconds, wait_s, on_open) -> Run:
    outstanding = gen.t["clients"]
    for c in range(outstanding):
        gen.send(time.perf_counter(), c)
    for _ in range(gen.warmup):
        rec, c = gen.take(timeout=600)
        if rec.error:
            raise RuntimeError(f"warm-up request failed: {rec.error}")
        gen.send(time.perf_counter(), c)
    t0 = time.perf_counter()
    on_open(t0)
    run = Run(t0, t0 + seconds)
    deadline = run.t1 + wait_s
    while outstanding:
        try:
            rec, c = gen.take(
                timeout=max(1e-3, deadline - time.perf_counter()))
        except queue.Empty:
            break
        outstanding -= 1
        gen.count(run, rec)
        now = time.perf_counter()
        if now < run.t1:
            run.records.append(gen.send(now, c))
            outstanding += 1
    return run
