"""Percentile, window-delta and lump arithmetic on fixed inputs."""

import math

import pytest

from harness import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [40, 10, 30, 20]
    assert stats.percentile(xs, 0) == 10
    assert stats.percentile(xs, 50) == 25
    assert stats.percentile(xs, 100) == 40
    assert stats.percentile(xs, 99) == pytest.approx(39.7)
    assert stats.percentile([7], 99) == 7
    assert stats.percentile([], 50) is None
    with pytest.raises(ValueError):
        stats.percentile(xs, 101)


def test_a_pod_that_never_bound_counts_as_infinite():
    xs = [1.0] * 98 + [float("inf")] * 2
    assert stats.percentile(xs, 50) == 1.0
    assert math.isinf(stats.percentile(xs, 99))


def test_window_delta_mean_is_delta_sum_over_delta_count():
    assert stats.window_delta_mean(100.0, 4, 160.0, 7) == 20.0
    assert stats.window_delta_mean(100.0, 4, 100.0, 4) is None


def test_lump_rate_measures_between_whole_lumps():
    s = 10 ** 9
    # the counter rises by 100 every second; polls every 0.5 s
    polls = [(int(t * s), 100 * int(t)) for t in
             (0.5, 1.0, 1.5, 2.0, 2.5, 3.0, 3.5, 4.0, 4.5, 5.0)]
    # window [1.2, 4.7): rises seen at 2.0, 3.0, 4.0 -> 200 pods in 2 s
    rate, lumps = stats.lump_rate(polls, int(1.2 * s), int(4.7 * s))
    assert (rate, lumps) == (100.0, 3)
    # one rise only: no rate
    assert stats.lump_rate(polls, int(1.2 * s), int(2.7 * s)) == (None, 1)


def test_interval_union_merges_overlaps_and_drops_empty():
    merged, total = stats.interval_union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)])
    assert merged == [(0, 4), (5, 12)]
    assert total == 11
