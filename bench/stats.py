"""The arithmetic that turns readings into metrics."""

from __future__ import annotations

import math
from fractions import Fraction
from typing import List


def nearest_rank(sorted_values: List[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of an ascending list: the
    element at rank ``ceil(q·n/100)``, 1-based, rank 1 for q=0.  (Copied
    from ``nearest_rank`` in ``src/repro/serving/vta/metrics.py``.)"""
    if not sorted_values:
        raise ValueError("percentile of an empty sample")
    if not 0 <= q <= 100:
        raise ValueError(f"quantile must be in [0, 100], got {q}")
    n = len(sorted_values)
    rank = max(1, math.ceil(Fraction(q) * n / 100))
    return sorted_values[min(rank, n) - 1]


def rate(count: int, seconds: float) -> float:
    """Events per second over a window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


def least_time_s(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the operations
    over the int8 peak and the bytes over the HBM bandwidth."""
    return max(ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def share_pct(least_s: float, taken_s: float):
    """``least_s`` as a percentage of ``taken_s``; None where nothing was
    taken (a share of nothing is no reading, and never 0)."""
    if taken_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / taken_s


def mfu_pct(ops_per_image: float, images_per_s: float, peak: dict):
    """Useful operations per second over the chip's int8 peak, in %."""
    if images_per_s <= 0:
        return None
    return 100.0 * ops_per_image * images_per_s / peak["int8_ops_per_s"]

