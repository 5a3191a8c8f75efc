"""Tail arithmetic over every sample of a run."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) of all ``values``, interpolated
    linearly between the two nearest ranks; None for no values."""
    if not values:
        return None
    xs = sorted(float(v) for v in values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values: Sequence[float]) -> Optional[float]:
    return float(statistics.fmean(values)) if values else None
