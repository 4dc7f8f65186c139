"""Percentiles and throughput over one window, as the metric readers
compute them."""
import numpy as np
import pytest

import chipbench_testkit  # noqa: F401
from chipbench import stats


def test_percentile_matches_numpy_on_finite_values():
    v = np.random.default_rng(0).exponential(1.0, 1001)
    for q in (50, 95, 99):
        assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_unanswered_requests_miss_every_limit():
    due = np.zeros(100)
    done = np.full(100, 0.01)
    done[:10] = np.nan                   # ten never answered
    lat = stats.latencies_s(due, done)
    assert stats.percentile(lat, 50) == pytest.approx(0.01)
    assert stats.percentile(lat, 95) == float("inf")


def test_a_stall_delays_every_request_due_during_it():
    """Requests due every 1 ms for 10 s; each is answered 2 ms after it
    was due, except during a 0.5 s stall at t = 5 s, when the server
    answers nothing and then everything queued at once."""
    due = np.arange(10_000) * 1e-3
    done = due + 2e-3
    stalled = (due >= 5.0) & (due < 5.5)
    done[stalled] = 5.5 + 2e-3
    lat = stats.latencies_s(due, done)
    assert stats.percentile(lat, 50) == pytest.approx(2e-3)
    # 5% of the requests were due during the stall, waiting 3 ms to 502 ms:
    # the 97.5th percentile lies half-way into them
    assert stats.percentile(lat, 97.5) == pytest.approx(0.252, abs=1e-4)
    assert lat.max() == pytest.approx(0.502)
    # the last two answers come after the window's end
    assert stats.throughput(done, 10.0) == pytest.approx(999.8, abs=0.15)


def test_throughput_counts_only_answers_inside_the_window():
    done = np.array([0.5, 1.0, 1.5, 2.0, 2.5, np.nan])
    assert stats.throughput(done, 2.0) == 2.0
