"""The load generator's common part: the queries a run sends, their
records, and the window.  How requests are sent is the traffic file's
``kind``: the generator ``bench/generators/<kind>.py`` (found by that name)
drives the window with ``drive(gen, seconds, wait_s, on_open) -> Run``.

The queries are drawn uniformly from the pool, in an order fixed by the
seed: the i-th request sent is ``pool[order[i]]`` whatever the timing, so
every seed sends the same sizes in another order.  Warm-up sends
``warmup_batches`` x ``batch`` requests through the same path before the
window opens.
"""

from __future__ import annotations

import dataclasses
import queue
import time
from typing import Callable, List, Optional

import numpy as np

from bench import registry

_ORDER_LEN = 1 << 20


@dataclasses.dataclass
class Record:
    """One request: pool row, due and done times (perf_counter seconds), and
    the answer (``None`` for a failed request, with ``error`` set)."""
    row: int
    due: float
    sent: float
    done: float = float("nan")
    ids: Optional[np.ndarray] = None
    scores: Optional[np.ndarray] = None
    error: Optional[str] = None


class Run:
    """Records of one window: requests sent in ``[t0, t1)``, and the count
    of replies of any request that came in ``[t0, t1)``."""

    def __init__(self, t0: float, t1: float):
        self.t0, self.t1 = t0, t1
        self.records: List[Record] = []
        self.replies_in_window = 0
        self.ok_in_window = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class LoadGen:
    """Sends the pool's queries to ``submit(query) -> Future`` in the
    seed's order, from one client thread, and settles their replies."""

    def __init__(self, traffic: dict, submit: Callable, pool: np.ndarray,
                 seed: int, batch: int):
        try:
            self._drive = registry.generator(traffic["kind"])
        except (OSError, ValueError) as e:
            raise ValueError(
                f"unknown traffic kind {traffic['kind']!r}: {e}") from None
        self.t = traffic
        self.submit = submit
        self.pool = pool
        self.batch = batch
        rng = np.random.default_rng([int(seed), 0x10AD])
        self.order = rng.integers(0, len(pool), _ORDER_LEN)
        self._n = 0
        self._done: "queue.SimpleQueue" = queue.SimpleQueue()

    @property
    def warmup(self) -> int:
        """Replies taken before the window opens."""
        return self.t.get("warmup_batches", 2) * self.batch

    def run(self, seconds: float, on_open: Callable = lambda t0: None,
            wait_s: float = 60.0) -> Run:
        """Warm up, call ``on_open(t0)`` as the window opens, drive the
        window, then wait up to ``wait_s`` for the requests sent in it."""
        return self._drive(self, seconds, wait_s, on_open)

    def send(self, due: float, tag) -> Record:
        """Send the next query of the order; its reply is queued for
        :meth:`take` with ``tag``."""
        row = int(self.order[self._n % _ORDER_LEN])
        self._n += 1
        rec = Record(row, due, time.perf_counter())
        fut = self.submit(self.pool[row])
        fut.add_done_callback(
            lambda f, r=rec, g=tag: self._done.put((r, g, time.perf_counter(),
                                                     f)))
        return rec

    def take(self, timeout: float) -> "tuple[Record, object]":
        """The next reply, settled into its record -> (record, tag);
        ``queue.Empty`` after ``timeout`` seconds."""
        rec, tag, t_done, fut = self._done.get(timeout=timeout)
        rec.done = t_done
        exc = fut.exception()
        if exc is not None:
            rec.error = repr(exc)
        else:
            res = fut.result()
            rec.ids = np.asarray(res[0])
            rec.scores = np.asarray(res[1])
        return rec, tag

    @staticmethod
    def count(run: Run, rec: Record) -> None:
        """Count a reply that came inside the window."""
        if run.t0 <= rec.done < run.t1:
            run.replies_in_window += 1
            if rec.error is None:
                run.ok_in_window += 1
