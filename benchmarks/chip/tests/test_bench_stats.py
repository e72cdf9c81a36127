"""Times per sweep over the whole window, and the spread of a set of runs."""
import pytest

from bench import stats


def test_sweeps_use_the_whole_window():
    assert stats.sweep_s(10.0, 4) == 2.5
    with pytest.raises(ValueError):
        stats.sweep_s(10.0, 0)


def test_spread_is_the_interquartile_share_of_the_median():
    assert stats.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    v = [9.0, 10.0, 10.0, 10.0, 10.0, 11.0]
    q1, q2, q3 = __import__("statistics").quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / q2)
