"""The load generator against a fake server: the closed loop keeps exactly
one request per client in flight, and the seed fixes what is sent."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench import loadgen


class FakeServer:
    """Answers in batches of ``batch`` (or whatever is queued after
    ``wait_s``) every ``step_s``, and records the most requests it ever
    held at once."""

    def __init__(self, batch=8, step_s=0.004, wait_s=0.002):
        self.batch, self.step_s, self.wait_s = batch, step_s, wait_s
        self.q = []
        self.lock = threading.Condition()
        self.held = 0
        self.max_held = 0
        self.sent = []
        self.stop = False
        self.t = threading.Thread(target=self._run, daemon=True)
        self.t.start()

    def submit(self, x):
        f = Future()
        with self.lock:
            self.q.append((np.asarray(x), f))
            self.sent.append(float(np.asarray(x)[0]))
            self.held += 1
            self.max_held = max(self.max_held, self.held)
            self.lock.notify()
        return f

    def _run(self):
        while True:
            with self.lock:
                self.lock.wait_for(lambda: self.q or self.stop, timeout=0.1)
                if self.stop:
                    return
                if len(self.q) < self.batch:
                    self.lock.wait(timeout=self.wait_s)
                take, self.q = self.q[:self.batch], self.q[self.batch:]
            time.sleep(self.step_s)
            for x, f in take:
                with self.lock:
                    self.held -= 1
                f.set_result((np.arange(3) + int(x[0]), np.ones(3)))

    def close(self):
        with self.lock:
            self.stop = True
            self.lock.notify()
        self.t.join(timeout=5)
        assert not self.t.is_alive()


POOL = np.arange(64, dtype=np.float32)[:, None] * np.ones((1, 4), np.float32)


def test_closed_loop_keeps_one_request_per_client():
    srv = FakeServer()
    try:
        gen = loadgen.LoadGen({"kind": "closed", "clients": 16,
                               "warmup_batches": 2}, srv.submit, POOL,
                              seed=5, batch=8)
        opened = []
        run = gen.run(0.3, on_open=opened.append, wait_s=5)
    finally:
        srv.close()
    assert srv.max_held <= 16
    assert opened == [run.t0]
    assert run.records and all(run.t0 <= r.sent < run.t1
                               for r in run.records)
    assert all(r.error is None and r.done >= r.sent for r in run.records)
    # every answer is the one for its own query
    assert all(int(r.ids[0]) == r.row for r in run.records)
    assert 0 < run.ok_in_window <= run.replies_in_window


def test_the_seed_fixes_the_order_of_queries():
    def sent(seed):
        srv = FakeServer(step_s=0.0005)
        try:
            gen = loadgen.LoadGen({"kind": "closed", "clients": 4,
                                   "warmup_batches": 1}, srv.submit, POOL,
                                  seed=seed, batch=2)
            gen.run(0.05, wait_s=5)
        finally:
            srv.close()
        return srv.sent

    a, b, c = sent(2**31 + 7), sent(2**31 + 7), sent(8)
    n = min(len(a), len(b))
    assert n > 8
    assert a[:n] == b[:n]
    assert a[:n] != c[:n]
    big = loadgen.LoadGen({"kind": "closed", "clients": 1}, None, POOL,
                          seed=2**33 + 1, batch=1)
    assert big.order.min() >= 0 and big.order.max() < len(POOL)


@pytest.mark.parametrize("kind", ["zipf", "open"])
def test_unknown_kind_is_refused(kind):
    with pytest.raises(ValueError):
        loadgen.LoadGen({"kind": kind}, None, POOL, seed=1, batch=1)
