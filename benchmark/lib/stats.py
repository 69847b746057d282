"""The few statistics the benchmark reports."""
from __future__ import annotations

import math


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile of `values` (q in [0, 1])."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("quantile of no values")
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# samples a reported tail has beyond it; benchmark/tests lifts it
BEYOND = 10


def highest_supported_percentile(n: int) -> float:
    """The highest percentile (as a fraction) of `n` samples that still
    has `BEYOND` samples above it; 0.5 when there are too few."""
    if n <= 2 * BEYOND:
        return 0.5
    return 1.0 - BEYOND / float(n)


def tail(values, want: float):
    """(value, percentile it is the value of): `want` when `BEYOND`
    samples lie above it, else the highest percentile that has them.
    A caller that reports under a name which states `want` must refuse
    the other case."""
    p = min(want, highest_supported_percentile(len(values)))
    return quantile(values, p), p
