"""Percentiles and spreads from raw samples (never from a histogram)."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(samples: Sequence[float], p: float) -> float:
    """The p-th percentile (0..100) of the raw samples, by linear
    interpolation between the two nearest order statistics."""
    if not len(samples):
        raise ValueError("percentile of no samples")
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile {p} outside 0..100")
    xs = sorted(float(x) for x in samples)
    pos = (len(xs) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 50.0)
