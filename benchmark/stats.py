"""Arithmetic on stamps and samples. Pure Python, no JAX: everything here
is checked on synthetic numbers in tests/test_benchmark.py."""
from __future__ import annotations

import statistics


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    the two nearest order statistics (numpy's default rule)."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def count_in(stamps, t0, t1):
    """How many stamps fall inside the closed window [t0, t1]."""
    return sum(1 for t in stamps if t0 <= t <= t1)


def gaps_ending_in(stamps, t0, t1):
    """Gaps between successive stamps of ONE request whose later stamp
    falls inside [t0, t1] (the earlier one may lie before the window)."""
    return [b - a for a, b in zip(stamps, stamps[1:]) if t0 <= b <= t1]


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives
    them: the rule a bound is set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
