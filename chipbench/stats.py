"""Percentile and spread arithmetic (copied in spirit from
benchmarks/perf.py: linear interpolation between order statistics)."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


class TooFewSamples(ValueError):
    """A tail was asked of a sample that cannot carry it."""


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between
    order statistics (numpy's default)."""
    if not values:
        raise TooFewSamples("percentile of an empty sample")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0 + 1e-9))


def tail(values: Sequence[float], q: float, min_beyond: int = 10) -> float:
    """percentile(), refused where fewer than `min_beyond` samples lie
    beyond it: a p95 of 60 requests is three requests' luck."""
    beyond = samples_beyond(len(values), q)
    if beyond < min_beyond:
        raise TooFewSamples(
            f"p{q:g} of {len(values)} samples has {beyond} beyond it, "
            f"needs {min_beyond}: lengthen the window or raise the rate"
        )
    return percentile(values, q)


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median, with the
    quartiles of statistics.quantiles(n=4) — the driver's reading."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else float("inf")
