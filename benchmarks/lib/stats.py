"""Percentile, rate and spread arithmetic, in one place so every cell
and every PR computes them the same way."""

from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default rule), in plain Python so the parent
    needs nothing but the standard library to print a metric."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over a window of no length")
    return count / seconds


def ratio(num: float, den: float) -> float | None:
    """num/den, None when there was nothing to divide by: a reader that
    finds nothing reports nothing."""
    return num / den if den else None


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)``: the rule the
    bounds are set by."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
