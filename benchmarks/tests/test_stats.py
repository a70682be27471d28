import statistics

import pytest

from lib import stats


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20, 30, 40, 50, 60, 70, 80, 90, 100, 110], 90, 100.0),
    ([5], 90, 5.0),
    ([3, 1, 2], 0, 1.0),
    ([3, 1, 2], 100, 3.0),
])
def test_percentile_interpolates_between_ranks(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_agrees_with_numpy():
    np = pytest.importorskip("numpy")
    xs = [float(x) for x in np.random.default_rng(0).lognormal(0, 1, 37)]
    for q in (10, 50, 90, 95):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


def test_rate_is_over_the_whole_window():
    assert stats.rate(510, 51) == 10.0
    with pytest.raises(ValueError):
        stats.rate(1, 0)


def test_ratio_reports_nothing_for_nothing():
    assert stats.ratio(3, 4) == 0.75
    assert stats.ratio(0, 0) is None


def test_spread_is_quartile_distance_over_median():
    xs = [100, 101, 102, 103, 104, 110]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == (q3 - q1) / statistics.median(xs)
