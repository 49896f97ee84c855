"""Percentiles, tails with missing requests, and the spread the bounds
are set from."""

import math
import statistics

# what a missing request counts as in a tail: a value no served request
# can reach, finite so that the result line stays JSON
MISSING_MS = 1e9


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tail_with_missing(values, n_missing, q, missing=MISSING_MS):
    """Percentile over served requests and ``n_missing`` requests that
    failed, were shed or never finished: each of those misses any
    limit, so it stands at ``missing``."""
    return percentile(list(values) + [missing] * int(n_missing), q)


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, by `statistics.quantiles(values, n=4)`: the contract's
    measure of run-to-run spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
