"""Percentiles and spreads, in plain Python.

``percentile`` interpolates linearly between order statistics, the
arithmetic of ``numpy.percentile``'s default (and of the program's
``serving/trace.py`` SLO report, copied here so the yardstick does not
move with the program).  ``spread`` is the quartile distance as a share
of the median, with the quartiles of ``statistics.quantiles(n=4)``.
"""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of ``xs``, linear interpolation."""
    if not xs:
        raise ValueError("percentile of no values")
    a = sorted(float(x) for x in xs)
    rank = (len(a) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(a) - 1)
    return a[lo] + (a[hi] - a[lo]) * (rank - lo)


def mean(xs: Sequence[float]) -> float:
    if not xs:
        raise ValueError("mean of no values")
    return math.fsum(xs) / len(xs)


def spread(xs: Sequence[float]) -> float:
    """(Q3 - Q1) / median, quartiles as ``statistics.quantiles`` gives them."""
    q1, _, q3 = statistics.quantiles(list(xs), n=4)
    return (q3 - q1) / statistics.median(xs)
