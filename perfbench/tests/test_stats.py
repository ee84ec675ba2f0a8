"""Nearest-rank percentiles and rates over a whole window."""
import pytest

from perfbench import stats


def test_nearest_rank():
    xs = [4, 1, 3, 2]
    assert stats.percentile(xs, 50) == 2
    assert stats.percentile(xs, 25) == 1
    assert stats.percentile(xs, 100) == 4
    assert stats.percentile(xs, 0) == 1
    assert stats.percentile(list(range(1, 11)), 90) == 9
    assert stats.percentile(list(range(1, 11)), 91) == 10


def test_percentile_keeps_a_miss_as_infinite():
    assert stats.percentile([1.0, float("inf")], 90) == float("inf")


def test_empty_sample_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_over_the_whole_window():
    assert stats.rate(300, 10.0, 13.0) == 100.0
    with pytest.raises(ValueError):
        stats.rate(1, 2.0, 2.0)
