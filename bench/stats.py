"""Percentiles of the drivers and metric readers."""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by the nearest-rank rule."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    k = max(1, math.ceil(q / 100.0 * len(v)))
    return float(v[k - 1])


def durations_line(ends: list) -> str:
    """Times between successive step ends (from 0) of a window, in ms: how
    many, their median, 90th percentile and largest, and how many took over
    1.25 times the median (stalls)."""
    if not ends:
        return "no steps"
    d = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    med = nearest_rank(d, 50)
    slow = sum(1 for x in d if x > 1.25 * med)
    return (f"{len(d)} steps, median {med * 1e3:.3f} ms, p90 "
            f"{nearest_rank(d, 90) * 1e3:.3f} ms, max {max(d) * 1e3:.3f} ms, "
            f"{slow} over 1.25x the median")
