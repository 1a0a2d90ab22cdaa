"""Percentiles and window arithmetic, on plain lists."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default method), of a non-empty sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def whole_units(boundaries: Sequence[Tuple[float, int]], start: float,
                deadline: float) -> Optional[Tuple[float, float, int]]:
    """The whole scheduling units inside a window.

    ``boundaries`` are (time, steps completed) at each report, in order. The
    window runs from the first boundary at or after ``start`` to the last
    boundary at or before ``deadline``. Returns (seconds, steps, units) or
    None where fewer than two boundaries lie inside."""
    inside = [(t, n) for t, n in boundaries if start <= t <= deadline]
    if len(inside) < 2:
        return None
    (t0, n0), (t1, n1) = inside[0], inside[-1]
    return t1 - t0, n1 - n0, len(inside) - 1


def quartile_spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median, with ``statistics.quantiles(n=4)``'s quartiles."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
