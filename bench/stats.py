"""Latency arithmetic (``latency_percentiles`` of ``benchmarks/common.py``,
copied so that the yardstick stays here)."""

from __future__ import annotations

import numpy as np


def percentile_ms(samples_s, q: float) -> float:
    """The ``q``-th percentile of samples in seconds, in milliseconds
    (``np.percentile``, linear interpolation)."""
    samples = np.asarray(list(samples_s), np.float64)
    if samples.size == 0:
        raise ValueError("no samples")
    return float(np.percentile(samples, q) * 1e3)

