"""Percentile and window arithmetic over wall-clock request records.

The percentile is the repo's ``serving/metrics.py`` arithmetic (numpy's
linear interpolation); here it is fed the benchmark's own wall-clock
stamps, never the scheduler's clock.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """``q``-th percentile (0-100) with linear interpolation."""
    a = np.asarray(list(values), dtype=np.float64)
    if a.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(a, q))


def beyond(values: Sequence[float], q: float) -> int:
    """How many samples lie strictly above the ``q``-th percentile."""
    p = percentile(values, q)
    return int(sum(1 for v in values if v > p))


def in_window(t: float, lo: float, hi: float) -> bool:
    """Half-open window membership ``lo <= t < hi``."""
    return lo <= t < hi


def tpot(first: float, last: float, n: int) -> float:
    """Seconds per output token after the first: (last - first) / (n - 1)."""
    if n < 2:
        raise ValueError("time per output token needs two tokens")
    return (last - first) / (n - 1)


def time_weighted_mean(values: Iterable[float],
                       weights: Iterable[float]) -> float:
    v = np.asarray(list(values), np.float64)
    w = np.asarray(list(weights), np.float64)
    if w.sum() <= 0:
        raise ValueError("no weight to average over")
    return float((v * w).sum() / w.sum())
