"""Randomized trace-conformance harness for the allocd admission daemon.

The daemon's contract (``src/repro/serving/allocd.py``): per tenant, the
flush-boundary equilibria it produces are BIT-EQUAL to an offline
``WindowSession.stream`` replay of that tenant's delivered events — under
multi-tenant interleaving, forced backpressure, mid-trace graceful drain
and mid-trace abort.  Plus the scheduling properties: slack-ordered
flushing across sessions and round-robin intake fairness.
"""
import asyncio
import itertools

import jax
import numpy as np
import pytest

from repro.core import (AdmissionWindow, CapacityEngine, ClassArrival,
                        FlushPolicy, Policies, RoundingPolicy, SolverConfig,
                        sample_class_params, sample_event_trace,
                        sample_scenario)
from repro.serving.allocd import (AllocDaemon, drive_open_loop,
                                  flash_crowd_times, interleave_traces,
                                  poisson_times, rejection_penalty)

B, N, N_MAX = 3, 4, 8          # one shared window shape: compile once


def make_engine(flush_k=3, slack=None):
    flush = (FlushPolicy.deadline(slack, max_events=flush_k)
             if slack is not None else FlushPolicy(max_events=flush_k))
    return CapacityEngine(SolverConfig(),
                          Policies(flush=flush,
                                   rounding=RoundingPolicy(enabled=False)))


def make_window(seed):
    key = jax.random.PRNGKey(seed)
    lanes = [sample_scenario(jax.random.fold_in(key, lane), N,
                             capacity_factor=1.3) for lane in range(B)]
    return AdmissionWindow(lanes, n_max=N_MAX)


def arrival(seed, E=None):
    params = dict(sample_class_params(jax.random.PRNGKey(seed)))
    if E is not None:
        params["E"] = E
    return ClassArrival(lane=seed % B, params=params)


def assert_reports_bitequal(got, want, *, prefix=False):
    if prefix:
        assert len(got) <= len(want)
    else:
        assert len(got) == len(want)
    for a, b in zip(got, want):
        la = jax.tree_util.tree_flatten(a.fractional)[0]
        lb = jax.tree_util.tree_flatten(b.fractional)[0]
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(a.iters),
                                      np.asarray(b.iters))
        np.testing.assert_array_equal(np.asarray(a.mask),
                                      np.asarray(b.mask))


def offline_replay(engine, seed, events):
    session = engine.open_window(make_window(seed))
    return list(session.stream(events))


async def submit_interleaved(daemon, traces, *, yield_between=True):
    """Round-robin submission; optionally let the scheduler interleave."""
    tickets = {name: [] for name in traces}
    for evs in itertools.zip_longest(*traces.values()):
        for name, ev in zip(traces, evs):
            if ev is not None:
                tickets[name].append(daemon.submit(name, ev))
        if yield_between:
            await asyncio.sleep(0)
    return tickets


# --------------------------------------------------------------------------
# Conformance: randomized multi-tenant traces
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_daemon_conformant_random_traces(seed):
    """Full random event mix (arrivals/departures/edits/capacity/bursts)
    through the daemon == offline per-tenant stream replays, bit-equal."""
    engine = make_engine(flush_k=3)
    traces = {f"t{i}": sample_event_trace(seed + 31 * i, make_window(i), 16)
              for i in range(3)}

    async def run():
        daemon = AllocDaemon(engine, queue_limit=None)
        for i in range(3):
            daemon.add_tenant(f"t{i}", make_window(i))
        await daemon.start()
        await submit_interleaved(daemon, traces)
        await daemon.shutdown(drain=True)
        return daemon

    daemon = asyncio.run(run())
    for i in range(3):
        want = offline_replay(engine, i, traces[f"t{i}"])
        assert_reports_bitequal(daemon.reports(f"t{i}"), want)
    rep = daemon.report()
    assert rep["rejected"] == 0
    assert rep["events_folded"] == sum(len(t) for t in traces.values())


def test_daemon_conformant_open_loop_schedules():
    """The timed (Poisson / flash-crowd) submission path conforms too."""
    engine = make_engine(flush_k=4)
    traces = {f"t{i}": sample_event_trace(11 + i, make_window(i), 8)
              for i in range(2)}
    times = poisson_times(3, 16, rate=5000.0)
    assert np.all(np.diff(times) >= 0)
    assert np.all(np.diff(flash_crowd_times(3, 100, 100.0)) >= 0)

    async def run():
        daemon = AllocDaemon(engine, queue_limit=64)
        for i in range(2):
            daemon.add_tenant(f"t{i}", make_window(i))
        await daemon.start()
        await drive_open_loop(daemon, interleave_traces(traces, times))
        await daemon.shutdown(drain=True)
        return daemon

    daemon = asyncio.run(run())
    assert daemon.rejected == 0
    for i in range(2):
        want = offline_replay(engine, i, traces[f"t{i}"])
        assert_reports_bitequal(daemon.reports(f"t{i}"), want)
    rep = daemon.report()
    assert rep["admission_p99_ms"] >= rep["admission_p50_ms"] >= 0.0


# --------------------------------------------------------------------------
# Backpressure
# --------------------------------------------------------------------------

def test_backpressure_rejects_with_penalty_and_stays_conformant():
    """Burst past the bounded queue: the overflow is rejected and charged
    the paper's rejection cost (m * H_up per arrival), and the ACCEPTED
    subtrace still replays bit-equal offline."""
    engine = make_engine(flush_k=4)
    # arrival-only trace: rejections cannot invalidate later events
    trace = [arrival(s) for s in range(12)]
    limit = 5

    async def run():
        daemon = AllocDaemon(engine, queue_limit=limit)
        daemon.add_tenant("t0", make_window(0))
        await daemon.start()
        # tight loop, no yield: the scheduler cannot drain between submits
        tickets = [daemon.submit("t0", ev) for ev in trace]
        await daemon.shutdown(drain=True)
        return daemon, tickets

    daemon, tickets = asyncio.run(run())
    rejected = [t for t in tickets if not t.accepted]
    accepted = [t for t in tickets if t.accepted]
    assert len(rejected) == len(trace) - limit
    want_cost = sum(rejection_penalty(t.event) for t in rejected)
    assert want_cost > 0.0
    assert daemon.rejection_cost == pytest.approx(want_cost)
    for t in rejected:
        assert t.report is None and t.penalty > 0.0
    want = offline_replay(engine, 0, [t.event for t in accepted])
    assert_reports_bitequal(daemon.reports("t0"), want)


def test_rejection_penalty_values():
    ev = arrival(0)
    assert rejection_penalty(ev) == pytest.approx(
        abs(float(ev.params["m"])) * abs(float(ev.params["H_up"])))
    from repro.core import ClassDeparture
    assert rejection_penalty(ClassDeparture(lane=0, slot=0)) == 0.0


# --------------------------------------------------------------------------
# Mid-trace shutdown: graceful drain and abort
# --------------------------------------------------------------------------

def test_mid_trace_graceful_drain_flushes_partial_epochs():
    """Stopping after a prefix: drain delivers everything queued and
    flushes the trailing partial epoch — exactly stream(prefix)."""
    engine = make_engine(flush_k=4)
    traces = {f"t{i}": sample_event_trace(41 + i, make_window(i), 13)
              for i in range(2)}
    half = {name: tr[:7] for name, tr in traces.items()}

    async def run():
        daemon = AllocDaemon(engine, queue_limit=None)
        for i in range(2):
            daemon.add_tenant(f"t{i}", make_window(i))
        await daemon.start()
        tickets = await submit_interleaved(daemon, half)
        await daemon.shutdown(drain=True)
        return daemon, tickets

    daemon, tickets = asyncio.run(run())
    for i in range(2):
        want = offline_replay(engine, i, half[f"t{i}"])
        assert_reports_bitequal(daemon.reports(f"t{i}"), want)
        # 7 events under flush_k=4: one full epoch + a drained partial
        assert len(daemon.reports(f"t{i}")) == 2
        for t in tickets[f"t{i}"]:
            assert t.report is not None and not t.cancelled


def test_mid_trace_abort_cancels_and_keeps_flushed_prefix():
    """drain=False: buffered/queued events are discarded, their tickets
    cancelled, and the reports so far are a bit-equal PREFIX of the full
    offline replay (sessions stay at their last flushed state)."""
    engine = make_engine(flush_k=4)
    trace = sample_event_trace(77, make_window(0), 11)

    async def run():
        daemon = AllocDaemon(engine, queue_limit=None)
        daemon.add_tenant("t0", make_window(0))
        await daemon.start()
        tickets = [daemon.submit("t0", ev) for ev in trace]
        # give the scheduler a few rounds, then yank the cord mid-trace
        for _ in range(8):
            await asyncio.sleep(0)
        await daemon.shutdown(drain=False)
        return daemon, tickets

    daemon, tickets = asyncio.run(run())
    session = daemon._tenants["t0"].session
    assert session.pending == ()          # buffers dropped, not half-applied
    cancelled = [t for t in tickets if t.cancelled]
    delivered = [t for t in tickets if t.report is not None]
    assert len(cancelled) + len(delivered) == len(trace)
    assert len(daemon.reports("t0")) >= 1   # it DID flush before the abort
    want = offline_replay(engine, 0, trace)
    assert_reports_bitequal(daemon.reports("t0"), want, prefix=True)
    with pytest.raises(RuntimeError):
        daemon.submit("t0", trace[0])     # closed daemons refuse work


def test_idle_daemon_shutdown_is_a_noop():
    """Draining a daemon that never saw an event performs no solve."""
    engine = make_engine()

    async def run():
        daemon = AllocDaemon(engine)
        daemon.add_tenant("t0", make_window(0))
        await daemon.start()
        await daemon.shutdown(drain=True)
        return daemon

    daemon = asyncio.run(run())
    assert daemon.reports("t0") == []
    assert daemon._tenants["t0"].session.flushes == 0
    assert daemon.report()["events_per_sec"] == 0.0


# --------------------------------------------------------------------------
# Scheduling: deadline ordering and fairness
# --------------------------------------------------------------------------

def test_due_sessions_flush_tightest_slack_first():
    """Two sessions due in the same round: the one holding the event with
    the least SLA slack (max E) re-equilibrates first."""
    engine = make_engine(flush_k=2)

    async def run():
        daemon = AllocDaemon(engine)
        daemon.add_tenant("loose", make_window(0))
        daemon.add_tenant("tight", make_window(1))
        await daemon.start()
        # both become due on their 2nd event, within one intake round
        daemon.submit("loose", arrival(0, E=-100.0))
        daemon.submit("tight", arrival(1, E=-1.0))
        daemon.submit("loose", arrival(2, E=-90.0))
        daemon.submit("tight", arrival(3, E=-50.0))
        await daemon.shutdown(drain=True)
        return daemon

    daemon = asyncio.run(run())
    assert [name for name, _ in daemon.flush_log] == ["tight", "loose"]
    slacks = dict(daemon.flush_log)
    assert slacks["tight"] == pytest.approx(1.0)   # min slack = -max(E)
    assert slacks["loose"] == pytest.approx(90.0)


def test_pending_slack_orders_sessions():
    engine = make_engine(flush_k=100)
    s = engine.open_window(make_window(0))
    assert s.pending_slack() == np.inf            # no deadline-carrying evs
    s.offer(arrival(0, E=-30.0))
    assert s.pending_slack() == pytest.approx(30.0)
    s.offer(arrival(1, E=-5.0))
    assert s.pending_slack() == pytest.approx(5.0)
    s.discard_pending()
    assert s.pending_slack() == np.inf


def test_round_robin_intake_is_fair_to_quiet_tenants():
    """A chatty tenant submitting 24 events before a quiet tenant's 4
    cannot starve it: round-robin intake interleaves from round one."""
    engine = make_engine(flush_k=1000)    # no auto-flush: pure intake order

    async def run():
        daemon = AllocDaemon(engine)
        daemon.add_tenant("chatty", make_window(0))
        daemon.add_tenant("quiet", make_window(1))
        await daemon.start()
        for s in range(24):
            daemon.submit("chatty", arrival(s))
        for s in range(4):
            daemon.submit("quiet", arrival(100 + s))
        await daemon.shutdown(drain=True)
        return daemon

    daemon = asyncio.run(run())
    last_quiet = max(i for i, n in enumerate(daemon.fold_log)
                     if n == "quiet")
    assert last_quiet <= 2 * 4             # interleaved, not appended
    assert daemon.fold_log.count("quiet") == 4
    assert daemon.fold_log.count("chatty") == 24


def test_critical_event_preempts_bulk_coalescing():
    """Under FlushPolicy.deadline, an SLA-critical arrival makes its
    session due immediately (mid-epoch) — through the daemon path too."""
    engine = make_engine(flush_k=50, slack=10.0)

    async def run():
        daemon = AllocDaemon(engine)
        daemon.add_tenant("t0", make_window(0))
        await daemon.start()
        daemon.submit("t0", arrival(0, E=-500.0))   # bulk: keeps buffering
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert daemon._tenants["t0"].session.flushes == 0
        daemon.submit("t0", arrival(1, E=-2.0))     # critical: E >= -10
        await daemon.shutdown(drain=True)
        return daemon

    daemon = asyncio.run(run())
    assert len(daemon.reports("t0")) == 1
    assert daemon._tenants["t0"].session.flushes == 1


# --------------------------------------------------------------------------
# Per-tenant quotas (PR 8) + arrival profiles
# --------------------------------------------------------------------------

def test_tenant_quota_engine_level_guards():
    """QuotaExceededError from the session layer: window wider than
    max_lanes at open, add_lane past the cap, and the offer backstop."""
    from repro.core import QuotaExceededError, TenantQuota
    engine = make_engine(flush_k=100)
    with pytest.raises(QuotaExceededError):
        engine.open_window(make_window(0), quota=TenantQuota(max_lanes=B - 1))

    session = engine.open_window(make_window(0),
                                 quota=TenantQuota(max_lanes=B))
    with pytest.raises(QuotaExceededError):
        session.add_lane(sample_scenario(jax.random.PRNGKey(9), N,
                                         capacity_factor=1.3))

    session = engine.open_window(make_window(1),
                                 quota=TenantQuota(max_queued=2))
    session.offer(arrival(0))
    session.offer(arrival(1))
    with pytest.raises(QuotaExceededError):
        session.offer(arrival(2))
    # the buffered epoch is still flushable after the refusal
    report = session.flush()
    assert report.fractional is not None


def test_per_tenant_quota_rejections_and_stats():
    """Quota exhaustion rejects with the paper penalty, is accounted per
    tenant, leaves other tenants untouched, and the accepted subtrace
    stays bit-equal to its offline replay."""
    from repro.core import TenantQuota
    engine = make_engine(flush_k=100)          # nothing flushes early
    events = [arrival(i) for i in range(5)]

    async def run():
        daemon = AllocDaemon(engine, queue_limit=64)
        daemon.add_tenant("capped", make_window(0),
                          quota=TenantQuota(max_queued=2))
        daemon.add_tenant("free", make_window(1))
        await daemon.start()
        capped = [daemon.submit("capped", ev) for ev in events]
        free = [daemon.submit("free", ev) for ev in events]
        await daemon.shutdown(drain=True)
        return daemon, capped, free

    daemon, capped, free = asyncio.run(run())
    assert [tk.accepted for tk in capped] == [True, True] + [False] * 3
    assert all(tk.accepted for tk in free)
    for tk in capped[2:]:
        assert tk.penalty == rejection_penalty(tk.event) > 0.0
    stats = daemon.tenant_stats("capped")
    assert stats["submitted"] == 5.0 and stats["rejected"] == 3.0
    assert stats["rejection_cost"] == pytest.approx(
        sum(tk.penalty for tk in capped[2:]))
    assert daemon.tenant_stats("free")["rejected"] == 0.0
    assert daemon.rejected == 3 and daemon.submitted == 10
    assert_reports_bitequal(
        daemon.reports("capped"),
        list(make_engine(flush_k=100).open_window(make_window(0))
             .stream(events[:2])))
    assert_reports_bitequal(
        daemon.reports("free"),
        list(make_engine(flush_k=100).open_window(make_window(1))
             .stream(events)))


def test_drain_tenant_is_single_tenant_graceful_drain():
    """drain_tenant folds ONE tenant's backlog and flushes its trailing
    partial — report list equals the full offline replay — while the
    other tenant's backlog is untouched until the daemon-wide drain."""
    engine = make_engine(flush_k=3)
    traces = {"a": [arrival(i) for i in range(5)],
              "b": [arrival(10 + i) for i in range(4)]}

    async def run():
        daemon = AllocDaemon(engine)
        daemon.add_tenant("a", make_window(0))
        daemon.add_tenant("b", make_window(1))
        await daemon.start()
        for name, evs in traces.items():
            for ev in evs:
                daemon.submit(name, ev)
        daemon.drain_tenant("a")
        reports_a = list(daemon.reports("a"))
        await daemon.shutdown(drain=True)
        return daemon, reports_a

    daemon, reports_a = asyncio.run(run())
    want_a = list(make_engine(flush_k=3).open_window(make_window(0))
                  .stream(traces["a"]))
    assert_reports_bitequal(reports_a, want_a)      # complete at drain time
    want_b = list(make_engine(flush_k=3).open_window(make_window(1))
                  .stream(traces["b"]))
    assert_reports_bitequal(daemon.reports("b"), want_b)


def test_diurnal_times_profile():
    """Sinusoidal modulation: monotone offsets, peak regions denser than
    troughs by roughly the peak factor."""
    from repro.serving.allocd import ARRIVAL_PROFILES, diurnal_times
    n = 2000
    times = diurnal_times(0, n, 10.0, peak_factor=4.0, cycles=2.0)
    assert times.shape == (n,)
    assert np.all(np.diff(times) > 0)
    gaps = np.diff(times)
    # cycles=2: troughs at k ~ 0 and n/2, peaks at k ~ n/4 and 3n/4
    trough = np.mean(gaps[: n // 20])
    peak = np.mean(gaps[n // 4 - n // 40: n // 4 + n // 40])
    assert trough / peak > 2.0
    # the daemon's original profiles survive in the shared library
    # (core/traces.py may carry more — tests/test_planning.py pins the set)
    assert {"poisson", "flash", "diurnal"} <= set(ARRIVAL_PROFILES)
    assert ARRIVAL_PROFILES["diurnal"] is diurnal_times


def test_poisoned_flush_is_reported_and_fails_the_launcher(monkeypatch,
                                                           tmp_path):
    """A flush that raises fails its tickets, keeps the daemon alive, is
    counted in ``report()["flush_errors"]``, and makes the launcher exit
    non-zero instead of printing throughput over failed epochs."""
    from repro.core.engine import WindowSession

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    from repro.launch import allocd as launch

    def poisoned(self):
        raise RuntimeError("poisoned epoch")

    monkeypatch.setattr(WindowSession, "flush", poisoned)

    async def run():
        daemon = AllocDaemon(make_engine(flush_k=2), queue_limit=None)
        daemon.add_tenant("t", make_window(0))
        await daemon.start()
        tickets = [daemon.submit("t", arrival(s)) for s in range(4)]
        await daemon.shutdown(drain=True)
        return daemon, tickets

    daemon, tickets = asyncio.run(run())
    assert all(tk.cancelled for tk in tickets)
    assert daemon.report()["flush_errors"] == 2.0
    assert launch.main(["--tenants", "1", "--lanes", "2", "--classes", "3",
                        "--events", "4", "--flush-every", "2"]) == 1


def test_launcher_fails_when_conformance_cannot_run(monkeypatch, tmp_path):
    """``--conformance`` under backpressure rejections cannot compare the
    delivered trace with the offline one: the launcher says so and exits
    non-zero rather than passing a check it skipped."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    from repro.launch import allocd as launch

    assert launch.main(["--tenants", "2", "--lanes", "2", "--classes", "3",
                        "--events", "6", "--queue-limit", "1",
                        "--rate", "1e9", "--conformance"]) == 1
