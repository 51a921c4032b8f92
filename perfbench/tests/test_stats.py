import math

import pytest

from perfbench import stats
from perfbench.drive import Record


def _rec(due, done, *, error=None, correct=True):
    return Record(index=0, size=16, due=due, t_done=done, error=error,
                  correct=correct)


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_is_from_due_time_over_every_due_request():
    records = [_rec(10.0 + i, 10.5 + i) for i in range(10)]
    records.append(_rec(9.0, 9.1))     # due before the window
    records.append(_rec(30.0, 30.1))   # due after it
    lat = stats.latencies_s(records, t0=10.0, t1=20.0)
    assert len(lat) == 10
    assert all(abs(x - 0.5) < 1e-9 for x in lat)


def test_failures_count_as_infinitely_late():
    records = [_rec(float(i), i + 0.01) for i in range(19)]
    records.append(_rec(19.0, 19.0, error="Overloaded"))
    lat = stats.latencies_s(records, t0=0, t1=100)
    assert stats.percentile(lat, 95) < 1
    assert stats.percentile(lat, 100) == math.inf
    records.append(_rec(20.0, None, error="NoAnswer", correct=None))
    records.append(_rec(21.0, 21.01, correct=False))   # wrong answer
    lat = stats.latencies_s(records, t0=0, t1=100)
    assert sum(x == math.inf for x in lat) == 3
    assert stats.percentile(lat, 95) == math.inf
    assert stats.percentile(lat, 50) < 1


def test_spread_is_the_interquartile_share_of_the_median():
    assert stats.spread([100, 100, 100, 100]) == 0
    assert abs(stats.spread([90, 95, 100, 105, 110]) - 0.15) < 1e-9
