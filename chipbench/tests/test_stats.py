import numpy as np
import pytest

from harness import stats


def test_percentile_nearest_rank_over_all_reads():
    lat = np.arange(1, 101, dtype=float) / 1000  # 1..100 ms
    failed = np.zeros(100, bool)
    assert stats.percentile(lat, failed, 50, 5.0) == pytest.approx(0.050)
    assert stats.percentile(lat, failed, 99, 5.0) == pytest.approx(0.099)
    assert stats.percentile(lat, failed, 100, 5.0) == pytest.approx(0.100)


def test_failed_reads_count_above_the_tail():
    lat = np.full(100, 0.001)
    failed = np.zeros(100, bool)
    failed[:2] = True  # two refusals, answered fast
    lat[:2] = 0.0
    # 2 of 100 failed: the 99th percentile lands on a failure, which
    # counts as the read timeout
    assert stats.percentile(lat, failed, 99, 5.0) == 5.0
    assert stats.percentile(lat, failed, 98, 5.0) == pytest.approx(0.001)
    assert stats.percentile(lat, failed, 50, 5.0) == pytest.approx(0.001)


def test_failed_read_never_below_a_slow_answer():
    lat = np.array([0.001, 7.0, 0.0])
    failed = np.array([False, False, True])
    assert stats.percentile(lat, failed, 100, 5.0) == 7.0


def test_rate_over_the_whole_window():
    assert stats.rate(1200, 10.0) == 120.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)

