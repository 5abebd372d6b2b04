"""Arithmetic on samples: exact percentiles, due-time latency, spread.

Exact samples on the benchmark's own clock; the program's histograms
(x1.5 log buckets) are too coarse for a bound of a few percent.
"""
from __future__ import annotations

import math


def percentile(samples, q):
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks; None for no samples."""
    xs = sorted(samples)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(samples):
    return percentile(samples, 50)


def spread(samples):
    """Distance between the quartiles over the median: what the driver
    calls a metric's spread over a set of runs."""
    m = median(samples)
    if not m:
        return None
    return (percentile(samples, 75) - percentile(samples, 25)) / abs(m)


def due_latencies_ms(due, done, end):
    """Latency of each request from the instant it was DUE (not from when
    the generator got round to sending it), in ms. `done[i]` is None for a
    request that was shed, failed or never answered: it waited at least
    until `end`, and ranks above every request that was answered then."""
    return [((d if d is not None else end) - t) * 1e3
            for t, d in zip(due, done)]
