"""Percentiles, rates and spreads: the benchmark's own arithmetic.

``percentile`` is a copy of ``repro.bench.timing.percentile`` (numpy's
linear interpolation between the two straddling order statistics), kept
here so that a change to the program cannot move the yardstick.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, numpy's default method."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    if not values:
        raise ValueError("percentile of an empty sequence")
    xs = sorted(float(v) for v in values)
    if len(xs) == 1:
        return xs[0]
    rank = q / 100.0 * (len(xs) - 1)
    lo = int(rank)
    frac = rank - lo
    if lo + 1 >= len(xs):
        return xs[-1]
    return xs[lo] + (xs[lo + 1] - xs[lo]) * frac


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def token_gaps(stamps: Dict[int, List[float]], t0: float, t1: float
               ) -> List[float]:
    """Every gap between consecutive tokens of one request that ends in
    the window [t0, t1), in the units of the stamps."""
    gaps = []
    for ts in stamps.values():
        for a, b in zip(ts, ts[1:]):
            if t0 <= b < t1:
                gaps.append(b - a)
    return gaps


def busy_union(intervals: Sequence[Tuple[float, float]], t0: float,
               t1: float) -> float:
    """Length of the union of ``intervals`` clipped to [t0, t1)."""
    total, end = 0.0, t0
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, t1)
        if b > a:
            total += b - a
            end = b
    return total
