"""The one generator of arrivals: a cell's traffic file names an arrival
process, its rate and the process's parameters, and this module turns
them and ``--seed`` into due times and frame picks.

``poisson_arrivals`` and ``bursty_arrivals`` are copies of the program's
``repro.fleet.traffic`` generators, kept here so the yardstick does not
move with the program.

Every seed gets the same amount of work: a window of ``seconds`` at rate
``r`` always holds ``round(r * seconds)`` requests.  The process draws one
arrival more than that, and the times are scaled so that the extra one
lands on the window's end.  For a Poisson process this is exact: given
the count, arrival times are uniform order statistics, which is what
normalising ``n + 1`` exponential gaps by their sum gives.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def poisson_arrivals(rate_hz: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """n exponential inter-arrival gaps at ``rate_hz``."""
    assert rate_hz > 0 and n > 0
    return np.cumsum(rng.exponential(1.0 / rate_hz, n))


def bursty_arrivals(rate_hz: float, n: int, rng: np.random.Generator, *,
                    burst_factor: float = 8.0, p_on: float = 0.2,
                    mean_run: int = 20) -> np.ndarray:
    """Two-state MMPP: bursts run ``burst_factor`` hotter than the quiet
    state; burst runs last ~``mean_run`` arrivals and hold ``p_on`` of all
    arrivals, so the long-run mean rate stays ``rate_hz``:
    E[gap] = p_on/r_on + (1-p_on)/r_off = 1/rate.
    """
    assert rate_hz > 0 and n > 0 and 0.0 < p_on < 1.0
    r_off = rate_hz * (p_on / burst_factor + (1.0 - p_on))
    r_on = burst_factor * r_off
    f_exit = 1.0 / mean_run                      # leave a burst
    f_enter = f_exit * p_on / (1.0 - p_on)       # enter a burst
    on = bool(rng.random() < p_on)
    # the per-arrival state chain decomposes into alternating runs with
    # geometric lengths, drawn in bulk and expanded to a state per arrival
    lens, states, covered = [], [], 0
    while covered < n:
        m = int(np.ceil((n - covered) / (1.0 / f_exit + 1.0 / f_enter))) + 16
        pair_len = np.empty(2 * m, np.int64)
        pair_on = np.empty(2 * m, bool)
        first, second = (f_exit, f_enter) if on else (f_enter, f_exit)
        pair_len[0::2] = rng.geometric(first, m)
        pair_len[1::2] = rng.geometric(second, m)
        pair_on[0::2], pair_on[1::2] = on, not on
        lens.append(pair_len)
        states.append(pair_on)
        covered += int(pair_len.sum())
    on_arr = np.repeat(np.concatenate(states), np.concatenate(lens))[:n]
    gaps = rng.exponential(1.0, n) / np.where(on_arr, r_on, r_off)
    return np.cumsum(gaps)


PROCESSES = {"poisson": poisson_arrivals, "bursty": bursty_arrivals}


@dataclass(frozen=True)
class Schedule:
    """Due times (seconds from the window's start, sorted, all inside the
    window) and, for each request, the index of the frame it sends."""
    due: np.ndarray
    pick: np.ndarray
    seconds: float

    def __len__(self) -> int:
        return len(self.due)


def make_schedule(traffic: dict, seconds: float, seed: int,
                  pool: int) -> Schedule:
    """The cell's arrivals for one window, from ``traffic`` (a cell's
    ``workloads/<cell>.json``) and the seed."""
    rate = float(traffic["rate_rps"])
    process = PROCESSES[traffic["arrivals"]]
    n = max(1, int(round(rate * seconds)))
    t = process(rate, n + 1, np.random.default_rng([seed, 1]),
                **traffic.get("process_args", {}))
    due = t[:n] * (seconds / t[n])
    pick = np.random.default_rng([seed, 2]).integers(0, pool, n)
    return Schedule(due, pick, float(seconds))
