"""Open-loop arrival schedules and tenant popularity.

A served cell offers exactly ``rate * seconds`` events in its window, so
every seed does the same amount of work in another order.  The shape of
the arrivals is named by the workload file's ``params.arrival``, a key of
:data:`ARRIVALS`; each generator returns ``n`` monotone offsets in
``[0, span)``.  ``bursty_times`` and ``flash_crowd_times`` are copies of
the program's generators (``repro.core.traces``), kept here so that a
change to the program cannot change the traffic it is measured with;
their schedules are scaled to end with the window.
"""
from __future__ import annotations

import numpy as np


def bursty_times(seed: int, n: int, rate: float, *,
                 burst_factor: float = 10.0, p_enter: float = 0.05,
                 p_exit: float = 0.25) -> np.ndarray:
    """Two-state Markov-modulated Poisson arrivals at mean ``rate``/s:
    quiet phases of ``1/p_enter`` events and bursts of ``1/p_exit`` events
    at ``burst_factor`` times the quiet rate."""
    rng = np.random.default_rng(seed)
    flips = rng.random(n)
    state = np.empty(n, dtype=bool)
    s = False
    for k in range(n):
        s = (flips[k] < p_enter) if not s else (flips[k] >= p_exit)
        state[k] = s
    mult = np.where(state, burst_factor, 1.0)
    gaps = rng.exponential(1.0, size=n) / mult
    gaps *= (n / rate) / np.sum(1.0 / mult)
    return np.cumsum(gaps)


def flash_crowd_times(seed: int, n: int, rate: float, *,
                      burst_factor: float = 8.0,
                      burst_frac: float = 0.4) -> np.ndarray:
    """Poisson arrivals at ``rate``/s whose middle ``burst_frac`` of events
    arrive ``burst_factor`` times faster."""
    rng = np.random.default_rng(seed)
    lo = int(n * (0.5 - burst_frac / 2.0))
    hi = int(n * (0.5 + burst_frac / 2.0))
    rates = np.full(n, rate, dtype=np.float64)
    rates[lo:hi] *= burst_factor
    return np.cumsum(rng.exponential(1.0, size=n) / rates)


def poisson_window(rng: np.random.Generator, n: int,
                   span: float) -> np.ndarray:
    """Exactly ``n`` Poisson arrivals in ``[0, span)``: a Poisson process
    conditioned on its count, the first ``n`` of ``n+1`` exponential gaps
    scaled so that the gaps sum to ``span``."""
    gaps = rng.exponential(1.0, size=n + 1)
    return np.cumsum(gaps)[:n] * (span / np.sum(gaps))


def _scaled(times_fn):
    """``times_fn(seed, n + 1, 1.0)`` scaled so its last arrival is
    ``span``, dropping that arrival."""
    def window(rng: np.random.Generator, n: int, span: float) -> np.ndarray:
        t = times_fn(int(rng.integers(2**63)), n + 1, 1.0)
        return t[:n] * (span / t[n])
    return window


#: Arrival shapes a workload file may name.
ARRIVALS = {"poisson": poisson_window,
            "bursty": _scaled(bursty_times),
            "flash": _scaled(flash_crowd_times)}


def zipf_tenants(rng: np.random.Generator, n_tenants: int, theta: float,
                 n: int) -> np.ndarray:
    """``n`` tenant indices with Zipfian popularity (``P(rank k)`` ~
    ``k^-theta``, YCSB's generator; theta 0 is uniform), the ranks permuted
    by ``rng``."""
    weights = 1.0 / np.arange(1, n_tenants + 1) ** theta
    rank_of = rng.permutation(n_tenants)
    ranks = rng.choice(n_tenants, size=n, p=weights / weights.sum())
    return rank_of[ranks]
