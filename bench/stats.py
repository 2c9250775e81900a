"""Percentiles of a pooled sample, computed here so that no later change to
the program's histograms can move them."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of ``values`` by linear interpolation
    between closest ranks, as ``numpy.percentile`` does by default."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)
