"""Percentiles, missing requests and the spread."""

import numpy as np
import pytest

from benchmarks.harness import stats


@pytest.mark.parametrize("q", [0, 25, 50, 95, 100])
def test_percentile_is_numpys(q):
    xs = list(np.random.default_rng(q).random(37))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_a_shed_request_misses_the_tail():
    served = [10.0] * 95
    assert stats.tail_with_missing(served, 0, 95) == 10.0
    # 6 of 101 missing: the 95th percentile lands on a missing request
    assert stats.tail_with_missing(served, 6, 95) == stats.MISSING_MS
    # 2 of 97 missing: still a served value
    assert stats.tail_with_missing(served, 2, 95) == 10.0


def test_spread_is_the_quartile_distance_over_the_median():
    import statistics

    xs = [100, 101, 102, 103, 104, 105]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 102.5)
