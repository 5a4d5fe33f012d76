"""Arithmetic of the end-to-end metrics: tails and rates.

Every tail is taken over all samples of the window (a request that never
finished counts as infinitely late), every rate over all the work and all
the time of the window.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of ``values`` by linear
    interpolation between closest ranks (numpy's default method).
    ``inf`` entries sort last, so unfinished requests push the tail up."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == math.inf:
        return math.inf if pos > lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def beyond(values, q: float) -> int:
    """How many samples lie strictly above the ``q``-th percentile: the
    tail's sample count (a tail needs ten or more to mean anything)."""
    p = percentile(values, q)
    return sum(1 for v in values if v > p)


def rate(work: float, seconds: float) -> float:
    """Work per second over a window of ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return work / seconds
