"""Statistics over raw samples: tails of a window, spreads of runs.

Tails are taken over every raw sample, never from histogram bucket edges
(which are 2-2.5x apart) and never as a median of per-chunk tails.
"""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) of ``values`` by linear
    interpolation between order statistics (rank ``q/100 * (n-1)``);
    None for no samples."""
    if not values:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    xs = sorted(float(v) for v in values)
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile, as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
