"""Percentile and spread arithmetic, kept with the yardstick."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear interpolation between order
    statistics."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 50.0)


def spread(samples: Sequence[float]) -> float:
    """Distance between the quartiles over the median: the driver's
    measure of how far runs of the same code disagree."""
    return (percentile(samples, 75.0) - percentile(samples, 25.0)) / median(samples)
