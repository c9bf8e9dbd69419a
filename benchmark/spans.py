"""Interval arithmetic shared by the metric readers and the trace
reduction: unions of [start, end) spans and their overlap with a
window."""
from __future__ import annotations

import numpy as np


def union(starts, ends) -> np.ndarray:
    """(k, 2) disjoint sorted intervals covering the given spans."""
    s = np.asarray(starts, np.float64)
    e = np.asarray(ends, np.float64)
    if not len(s):
        return np.zeros((0, 2))
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    # a new interval starts where a span begins past everything before it
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.nonzero(new)[0]
    last = np.append(first[1:] - 1, len(s) - 1)
    return np.stack([s[first], reach[last]], 1)


def covered(intervals: np.ndarray, lo: float, hi: float) -> float:
    """Length of [lo, hi) that the disjoint ``intervals`` cover."""
    if not len(intervals):
        return 0.0
    a = np.clip(intervals[:, 0], lo, hi)
    b = np.clip(intervals[:, 1], lo, hi)
    return float(np.sum(b - a))


def gaps(intervals: np.ndarray) -> np.ndarray:
    """(k-1, 2) spaces between consecutive disjoint intervals."""
    if len(intervals) < 2:
        return np.zeros((0, 2))
    return np.stack([intervals[:-1, 1], intervals[1:, 0]], 1)


def busy_pct(spans: np.ndarray, lo: float, hi: float) -> float:
    """Share (%) of [lo, hi) inside any of the (n, 2) spans."""
    u = union(spans[:, 0], spans[:, 1]) if len(spans) else np.zeros((0, 2))
    return 100.0 * covered(u, lo, hi) / (hi - lo)
