"""End-to-end statistics over every read due in the window."""

from __future__ import annotations

import math

import numpy as np


def percentile(latencies_s: np.ndarray, failed: np.ndarray, p: float, timeout_s: float) -> float:
    """Nearest-rank ``p``-th percentile, in seconds, over all reads. A
    failed read (refused, shed, timed out or never answered) counts as
    the read timeout, which lies above every latency a read may have."""
    lat = np.where(failed, max(timeout_s, float(np.max(latencies_s, initial=0.0))), latencies_s)
    lat = np.sort(lat)
    if lat.size == 0:
        raise ValueError("no reads")
    rank = max(1, math.ceil(p / 100.0 * lat.size))
    return float(lat[rank - 1])


def rate(completed: int, window_s: float) -> float:
    """Reads completed per second over the whole window."""
    if window_s <= 0:
        raise ValueError("empty window")
    return completed / window_s

