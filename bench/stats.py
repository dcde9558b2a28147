"""Exact statistics over a run's samples: no buckets, no chunk medians."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) of all samples by the nearest-rank
    rule: the smallest sample with at least ``q`` percent of the samples at
    or below it. Exact, with no interpolation between samples."""
    if not samples:
        raise ValueError("percentile of no samples")
    if not 0 < q <= 100:
        raise ValueError(f"q must lie in (0, 100], got {q}")
    ordered = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate(completed: int, seconds: float) -> float:
    """Work completed per second over the whole window."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return completed / seconds


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``, the rule the bounds are
    set by)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
