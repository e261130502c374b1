"""Percentiles and rates, as the benchmark defines them."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) of ``values`` by linear interpolation
    between the two nearest ranks (numpy's default method)."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside [0, 100]")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate_in_window(events, start: float, end: float) -> float:
    """Units completed per second over ``[start, end]``.

    ``events`` holds ``(t0, t1, units)``: ``units`` completed by work that ran
    from ``t0`` to ``t1``. Work that straddles a bound counts in proportion to
    its part inside the window, so the rate is over exactly the window."""
    if end <= start:
        raise ValueError("empty window")
    done = 0.0
    for t0, t1, units in events:
        if t1 <= start or t0 >= end:
            continue
        if t1 <= t0:
            done += units
            continue
        inside = min(t1, end) - max(t0, start)
        done += units * inside / (t1 - t0)
    return done / (end - start)
