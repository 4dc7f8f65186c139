"""Arithmetic over one window's requests and steps, on the host clock.

Times are seconds from the window's start.  A request that never got an
answer has completion time NaN: it counts as missing every latency limit,
so it enters a percentile as an infinite latency.
"""
from __future__ import annotations

import numpy as np


def latencies_s(due: np.ndarray, done: np.ndarray) -> np.ndarray:
    """Latency of each request, from when it was due (not when it was
    submitted: a stall delays later requests, and that wait counts)."""
    lat = np.asarray(done, float) - np.asarray(due, float)
    return np.where(np.isnan(lat), np.inf, lat)


def percentile(values: np.ndarray, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between order
    statistics (numpy's default); infinite where the rank falls among
    requests that never got an answer."""
    v = np.sort(np.asarray(values, float))
    if not len(v):
        return float("nan")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if np.isinf(v[hi]):
        return float("inf")
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def throughput(done: np.ndarray, seconds: float) -> float:
    """Requests completed inside the window, per second of window."""
    d = np.asarray(done, float)
    return float(np.count_nonzero(d <= seconds) / seconds)
