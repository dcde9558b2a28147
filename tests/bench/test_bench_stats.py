"""Exact percentiles, rates and spreads (``bench/stats.py``)."""

import statistics

import pytest

from bench import stats


@pytest.mark.parametrize("q, want", [(50, 5), (95, 10), (100, 10), (10, 1),
                                     (1, 1), (51, 6)])
def test_percentile_is_the_nearest_rank_sample(q, want):
    assert stats.percentile(list(range(10, 0, -1)), q) == want


def test_percentile_of_many_samples_counts_every_one():
    samples = [float(i) for i in range(1, 1001)]
    assert stats.percentile(samples, 95) == 950.0
    assert stats.percentile(samples, 50) == 500.0
    assert stats.percentile(samples + [1e9], 99.9) == 1000.0


@pytest.mark.parametrize("bad", [0, -1, 101])
def test_percentile_rejects_q_outside_the_range(bad):
    with pytest.raises(ValueError):
        stats.percentile([1.0], bad)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_work_over_the_whole_window():
    assert stats.rate(107, 30.0) == pytest.approx(107 / 30)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_spread_is_the_quartile_distance_over_the_median():
    values = [100, 101, 102, 103, 104, 105]
    q1, med, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / med)
    assert stats.spread([5.0] * 6) == 0.0
