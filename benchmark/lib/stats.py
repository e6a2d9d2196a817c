"""Percentiles, spreads and window arithmetic.  Pure stdlib."""

import math
import statistics


def percentile(values, q):
    """Nearest-rank percentile of ``values`` at ``q`` in [0, 1]: the
    smallest sample with at least ``q`` of the samples at or below it.
    (The program's ``_pct`` rounds an index instead; for the sample counts
    a window holds the two agree to within one sample.)  An empty list has
    no percentile and gives None."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[min(rank, len(ordered)) - 1]


def samples_beyond(n, q):
    """How many of ``n`` samples lie above the ``q`` percentile: the
    choosing-metrics rule wants at least ten."""
    return n - max(1, math.ceil(q * n))


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, with the quartiles ``statistics.quantiles(values, n=4)``
    gives: the contract's definition of a spread."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
