"""The arithmetic of the end-to-end metrics."""

import math

import pytest

import bench_checkout  # noqa: F401 — puts the harness on the path
from harness import stats


def test_build_rate_is_all_vectors_over_all_time():
    # three whole builds of 250,000 in 51.5 s
    assert stats.rate(250_000, 3, 51.5) == pytest.approx(750_000 / 51.5)
    with pytest.raises(ValueError):
        stats.rate(1, 1, 0.0)


def test_percentile_is_timed_from_due():
    due = [0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0]
    # each served 0.1 s after it was sent, but the sender ran 0.5 s late on the last
    done = [d + 0.1 for d in due[:-1]] + [9.0 + 0.5 + 0.1]
    assert stats.percentile_from_due(due, done, 90) == pytest.approx(0.1)
    assert stats.percentile_from_due(due, done, 100) == pytest.approx(0.6)


def test_missing_requests_lie_above_every_served_one():
    due = [float(i) for i in range(10)]
    done = [d + 0.2 for d in due]
    done[3] = math.nan
    assert stats.percentile_from_due(due, done, 90) == pytest.approx(0.2)
    done[4] = math.nan
    assert stats.percentile_from_due(due, done, 90) == math.inf
