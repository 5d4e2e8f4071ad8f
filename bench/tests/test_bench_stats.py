"""The percentile and spread arithmetic."""
import tiny  # noqa: F401

import statistics

import numpy as np
import pytest

from bench.harness import stats


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_is_numpys_linear_interpolation(q):
    xs = list(np.random.default_rng(3).exponential(size=101))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_spread_uses_statistics_quartiles():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


def test_empty_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
