"""Metric arithmetic on synthetic records: a rate is all the work of the
window over all of its time, a tail is the tail of every event."""
from __future__ import annotations

import math
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.lib import checks, readers, schedule, stats  # noqa: E402


def test_percentile_matches_numpy():
    xs = list(np.random.default_rng(0).exponential(size=501))
    for q in (50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], 50) is None


def test_failed_requests_enter_the_tail_as_infinite():
    xs = [1.0] * 90 + [math.inf] * 10
    assert stats.percentile(xs, 50) == 1.0
    assert stats.percentile(xs, 95) == math.inf


def test_rate_is_all_work_over_all_time():
    assert stats.rate(300, 20.0) == 15.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def _ticket(t_done, ok=True):
    return SimpleNamespace(t_done=t_done, report=object() if ok else None)


def test_served_window_arithmetic():
    """20 events over a 10 s window from t0 = 100: 15 answered inside it,
    4 after it (the backlog), 1 failed."""
    t0, seconds = 100.0, 10.0
    at = np.linspace(0.0, 9.5, 20)
    done = [t0 + a + 0.05 for a in at[:15]] + [t0 + 11.0 + k
                                               for k in range(4)]
    tickets = [_ticket(d) for d in done] + [_ticket(t0 + 12.0, ok=False)]
    sent = [t0 + a + 0.001 * k for k, a in enumerate(at)]
    spec = {"seconds": seconds}
    out = checks.served_result(spec, [], [], [], t0, t0 + seconds, at,
                               tickets, sent, {}, never=0)
    v = out["values"]
    assert out["attempted"] == 20 and out["failed"] == 1
    assert v["events_per_s"] == pytest.approx(15 / seconds)
    assert v["backlog_events"] == 5
    lat = [d - (t0 + a) for d, a in zip(done, at)] + [math.inf]
    assert v["admission_p50_ms"] == pytest.approx(
        np.percentile(lat[:-1] + [1e9], 50) * 1e3)
    assert v["admission_p95_ms"] == pytest.approx(
        stats.percentile(lat, 95) * 1e3)
    assert v["gen_lag_p95_ms"] == pytest.approx(
        np.percentile([0.001 * k for k in range(20)], 95) * 1e3)
    assert math.isnan(out["readings"]["r_rel_l1"])


def test_readers_find_nothing_without_a_trace():
    run = {"values": {"backlog_events": 3.0}}
    assert readers.solve_ms_per_run(run) is None
    assert readers.idle_pct(run) is None
    assert readers.value(run, "backlog_events") == 3.0
    traced = {"trace": {"window_s": 2.0, "busy_s": 0.5, "programs": {
        "solve": {"device_s": 0.1, "runs": 50.0}}}}
    assert readers.idle_pct(traced) == pytest.approx(75.0)
    assert readers.solve_ms_per_run(traced) == pytest.approx(2.0)


def test_a_missing_or_nan_reading_fails():
    v = checks.verdict({"a": 0.5, "b": float("nan")},
                       {"a": 1.0, "b": 1.0, "c": 0})
    assert not checks.passed(v)
    assert checks.passed(checks.verdict({"a": 0.5}, {"a": 1.0}))


@pytest.mark.parametrize("arrival", sorted(schedule.ARRIVALS))
def test_every_arrival_shape_fills_the_window(arrival):
    rng = np.random.default_rng(2**33 + 1)
    t = schedule.ARRIVALS[arrival](rng, 600, 30.0)
    assert t.shape == (600,) and np.all(np.diff(t) >= 0)
    assert 0.0 <= t[0] and t[-1] < 30.0


def test_zipf_popularity_and_uniform():
    rng = np.random.default_rng(7)
    hot = np.bincount(schedule.zipf_tenants(rng, 32, 0.99, 20000),
                      minlength=32)
    flat = np.bincount(schedule.zipf_tenants(rng, 32, 0.0, 20000),
                       minlength=32)
    assert hot.max() / 20000 > 0.2 and flat.max() / 20000 < 0.05
