"""The benchmark's arrival generator: seeded, and it meets the rate."""
import numpy as np
import pytest

import chipbench_testkit  # noqa: F401  (puts the checkout on sys.path)
from chipbench import traffic as T

POISSON = {"arrivals": "poisson", "rate_rps": 2000.0}
BURSTY = {"arrivals": "bursty", "rate_rps": 2000.0,
          "process_args": {"burst_factor": 8.0, "p_on": 0.2, "mean_run": 20}}


@pytest.mark.parametrize("traffic", [POISSON, BURSTY], ids=["poisson", "bursty"])
def test_same_seed_same_schedule(traffic):
    a = T.make_schedule(traffic, 3.0, 2 ** 31 + 7, 64)
    b = T.make_schedule(traffic, 3.0, 2 ** 31 + 7, 64)
    c = T.make_schedule(traffic, 3.0, 2 ** 31 + 8, 64)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.pick, b.pick)
    assert not np.array_equal(a.due, c.due)


@pytest.mark.parametrize("traffic", [POISSON, BURSTY], ids=["poisson", "bursty"])
def test_every_seed_gets_the_rate_inside_the_window(traffic):
    for seed in (1, 2, 3):
        s = T.make_schedule(traffic, 2.5, seed, 64)
        assert len(s) == 5000
        assert np.all(np.diff(s.due) >= 0)
        assert 0 <= s.due[0] and s.due[-1] < 2.5
        assert s.pick.min() >= 0 and s.pick.max() < 64


@pytest.mark.parametrize("process", ["poisson", "bursty"])
def test_processes_meet_their_mean_rate(process):
    t = T.PROCESSES[process](500.0, 200_000, np.random.default_rng(5))
    assert t[-1] / len(t) == pytest.approx(1 / 500.0, rel=0.03)


def test_bursty_is_burstier_than_poisson():
    """Same mean rate, but the MMPP's gaps spread far wider."""
    rng = np.random.default_rng(9)
    gp = np.diff(T.poisson_arrivals(1000.0, 100_000, rng))
    gb = np.diff(T.bursty_arrivals(1000.0, 100_000, rng))
    cv = lambda g: g.std() / g.mean()   # noqa: E731
    assert cv(gp) == pytest.approx(1.0, abs=0.03)
    assert cv(gb) > 1.1


def test_copies_match_the_programs_generators():
    """The copies draw exactly what ``repro.fleet.traffic`` draws."""
    from repro.fleet import traffic as program

    for ours, theirs in ((T.poisson_arrivals, program.poisson_arrivals),
                         (T.bursty_arrivals, program.bursty_arrivals)):
        a = ours(300.0, 1000, np.random.default_rng(3))
        b = theirs(300.0, 1000, np.random.default_rng(3))
        assert np.array_equal(a, b)
