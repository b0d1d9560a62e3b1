"""Event-coalescing + dynamic-window tests.

Core guarantees under test:

* ``AdmissionWindow.apply_epoch`` is bit-identical to applying the same
  events one by one with ``apply`` (slot grants, mask, every Scenario leaf,
  raw-parameter book-keeping), and is atomic under invalid events;
* a coalesced replay (``WindowSession.stream``) lands on the per-event
  equilibria at every flush boundary — including across window growth, lane
  add/remove, compaction and under a device mesh (<= 1e-6, matching the
  PR 2 convention; checked against a cold ``solve_distributed_batch`` of
  the same window, the ground truth both paths must agree with);
* ``compact()`` remaps stored equilibria and warm starts so clean lanes
  stay *frozen* (zero iterations) through the re-layout;
* ``FlushPolicy`` triggers on event count, dirty-lane fraction, and —
  deadline-aware (``FlushPolicy.deadline``) — on SLA-critical events.
"""
import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import (AdmissionWindow, CapacityEngine, ClassArrival,
                        ClassDeparture, CrossCheckPolicy, EventEpoch,
                        FlushPolicy, Policies, RoundingPolicy, SLAEdit,
                        SolverConfig, lane_mesh, replay, sample_class_params,
                        sample_event_trace, sample_scenario,
                        solve_distributed_batch)


def solve_streaming(window, *, integer=True, mesh=None, cross_check=False):
    """Engine-path stand-in for the retired allocator.solve_streaming facade
    (shims themselves are covered by tests/test_engine.py)."""
    return CapacityEngine(
        SolverConfig(mesh=mesh),
        Policies(rounding=RoundingPolicy(integer),
                 cross_check=CrossCheckPolicy(cross_check))
    ).open_window(window).solve()


def solve_coalesced(window, events, *, policy=None, integer=True, mesh=None):
    """Engine-path stand-in for the retired allocator.solve_coalesced
    facade: a ``WindowSession.stream`` generator."""
    eng = CapacityEngine(
        SolverConfig(mesh=mesh),
        Policies(flush=policy if policy is not None else FlushPolicy(),
                 rounding=RoundingPolicy(integer)))
    return eng.open_window(window).stream(events)

D = jax.device_count()
needs_devices = pytest.mark.skipif(
    D < 2, reason="needs >= 2 devices (conftest forces 8 on CPU)")

SCN_FIELDS = ("A", "B", "E", "r_low", "r_up", "p", "alpha", "beta", "K",
              "rho_up", "rho_hat", "R", "rho_bar")


def make_window(ns=(5, 8, 3, 6), cf=1.2, n_max=None, seed0=0):
    scns = [sample_scenario(jax.random.PRNGKey(seed0 + i), n,
                            capacity_factor=cf)
            for i, n in enumerate(ns)]
    return AdmissionWindow(scns, n_max=n_max)


def assert_windows_identical(w1, w2):
    np.testing.assert_array_equal(w1._mask, w2._mask)
    for f in SCN_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(w1._scn, f)),
                                      np.asarray(getattr(w2._scn, f)), f)
    assert w1._raw == w2._raw
    np.testing.assert_array_equal(w1.dirty, w2.dirty)


def assert_equiv_cold(window, res, tol=1e-6):
    """Streaming/coalesced result == cold batched re-solve of the window."""
    cold = solve_distributed_batch(window.batch)
    np.testing.assert_allclose(np.asarray(res.fractional.r),
                               np.asarray(cold.r), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(res.fractional.total),
                               np.asarray(cold.total), rtol=tol)
    np.testing.assert_array_equal(np.asarray(res.iters),
                                  np.asarray(cold.iters))
    np.testing.assert_array_equal(np.asarray(res.feasible),
                                  np.asarray(cold.feasible))


# --------------------------------------------------------------------------
# apply_epoch == sequential apply
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_apply_epoch_matches_sequential(seed):
    """Coalesced application is bit-identical to event-by-event apply,
    including growth past n_max and in-epoch slot recycling."""
    w_seq, w_co = make_window(n_max=9, seed0=3 * seed), \
        make_window(n_max=9, seed0=3 * seed)
    trace = sample_event_trace(40 + seed, w_seq, 30)
    seq_slots = [w_seq.apply(ev) for ev in trace]
    co_slots = w_co.apply_epoch(trace)
    assert seq_slots == co_slots
    assert_windows_identical(w_seq, w_co)
    assert any(s is not None for s in co_slots)


def test_apply_epoch_folds_arrive_edit_depart_chain():
    """In-epoch chains (arrive -> edit -> depart of the same slot) fold to
    the same net state sequential application produces."""
    w_seq, w_co = make_window(ns=(3, 4)), make_window(ns=(3, 4))
    p1 = sample_class_params(jax.random.PRNGKey(0))
    p2 = sample_class_params(jax.random.PRNGKey(1))
    events = [
        ClassArrival(lane=0, params=p1),             # -> slot 3
        SLAEdit(lane=0, slot=3, updates={"E": -450.0, "m": 31000.0}),
        ClassDeparture(lane=0, slot=0),
        ClassArrival(lane=0, params=p2),             # recycles slot 0
        ClassDeparture(lane=0, slot=3),              # in-epoch class leaves
        ClassDeparture(lane=1, slot=2),
    ]
    for ev in events:
        w_seq.apply(ev)
    w_co.apply_epoch(events)
    assert_windows_identical(w_seq, w_co)
    assert w_co.occupied(0) == [0, 1, 2]


def test_apply_epoch_is_atomic():
    """An invalid event anywhere in the epoch raises before ANY mutation."""
    w = make_window(ns=(3, 4))
    before_mask = w._mask.copy()
    before_A = np.asarray(w._scn.A).copy()
    good = ClassArrival(lane=1, params=sample_class_params(
        jax.random.PRNGKey(2)))
    with pytest.raises(IndexError):
        w.apply_epoch([good, ClassDeparture(lane=0, slot=3)])  # empty slot
    with pytest.raises(ValueError):
        w.apply_epoch([good, SLAEdit(lane=0, slot=0, updates={"nope": 1.0})])
    with pytest.raises(ValueError):
        w.apply_epoch([ClassArrival(lane=0, params={"A": 1.0})])
    with pytest.raises(TypeError):
        w.apply_epoch([good, "not-an-event"])
    np.testing.assert_array_equal(w._mask, before_mask)
    np.testing.assert_array_equal(np.asarray(w._scn.A), before_A)
    assert not w.dirty.any()
    assert w.apply_epoch([]) == []


# --------------------------------------------------------------------------
# apply_epoch invariants as PROPERTIES (hypothesis; loud skip without it)
# --------------------------------------------------------------------------

@settings(deadline=None, max_examples=15, derandomize=True)
@given(seed=st.integers(0, 10_000), n_events=st.integers(1, 40),
       p_depart=st.floats(0.0, 0.6), n_max=st.sampled_from([9, 12, 16]))
def test_property_apply_epoch_matches_sequential(seed, n_events, p_depart,
                                                 n_max):
    """For ANY seeded trace (length, churn mixture, headroom — including
    traces that force growth and in-epoch slot recycling), one coalesced
    apply_epoch is bit-identical to event-by-event apply."""
    w_seq = make_window(n_max=n_max, seed0=seed % 7)
    w_co = make_window(n_max=n_max, seed0=seed % 7)
    trace = sample_event_trace(seed, w_seq, n_events, p_depart=p_depart)
    seq_slots = [w_seq.apply(ev) for ev in trace]
    co_slots = w_co.apply_epoch(trace)
    assert seq_slots == co_slots
    assert_windows_identical(w_seq, w_co)


@settings(deadline=None, max_examples=15, derandomize=True)
@given(seed=st.integers(0, 10_000), n_good=st.integers(0, 12),
       bad_kind=st.sampled_from(["empty-slot", "bad-field", "missing",
                                 "bad-lane"]))
def test_property_apply_epoch_atomic_under_any_prefix(seed, n_good,
                                                      bad_kind):
    """An invalid event after ANY valid prefix aborts the whole epoch
    with ZERO mutation — mask, scenario leaves, raw registry, dirt."""
    w = make_window(ns=(3, 4), n_max=8)
    good = sample_event_trace(seed, w, n_good, p_depart=0.0, p_edit=0.0,
                              p_capacity=0.0) if n_good else []
    # the prefix's arrivals may fill any fixed slot, so the empty slot is
    # read off a twin window that has applied the prefix (the slot just
    # past the row when the prefix leaves every slot taken)
    twin = make_window(ns=(3, 4), n_max=8)
    twin.apply_epoch(good)
    free = np.argwhere(~twin._mask)
    lane, empty = free[0] if len(free) else (0, twin.n_max)
    bad = {"empty-slot": ClassDeparture(lane=int(lane), slot=int(empty)),
           "bad-field": SLAEdit(lane=0, slot=0, updates={"nope": 1.0}),
           "missing": ClassArrival(lane=0, params={"A": 1.0}),
           "bad-lane": ClassDeparture(lane=99, slot=0)}[bad_kind]
    before_mask = w._mask.copy()
    before_raw = dict(w._raw)
    before_A = np.asarray(w._scn.A).copy()
    with pytest.raises((IndexError, ValueError)):
        w.apply_epoch([*good, bad])
    np.testing.assert_array_equal(w._mask, before_mask)
    np.testing.assert_array_equal(np.asarray(w._scn.A), before_A)
    assert w._raw == before_raw
    assert not w.dirty.any()


# --------------------------------------------------------------------------
# Flush policies + EventEpoch
# --------------------------------------------------------------------------

def test_flush_policy_triggers():
    count = FlushPolicy(max_events=3)
    assert not count.should_flush(n_events=2, n_dirty=2, batch_size=4)
    assert count.should_flush(n_events=3, n_dirty=0, batch_size=4)
    frac = FlushPolicy(max_events=None, max_dirty_fraction=0.5)
    assert not frac.should_flush(n_events=100, n_dirty=1, batch_size=4)
    assert frac.should_flush(n_events=1, n_dirty=2, batch_size=4)
    manual = FlushPolicy(max_events=None, max_dirty_fraction=None)
    assert not manual.should_flush(n_events=10 ** 6, n_dirty=4, batch_size=4)


def test_event_epoch_accumulates_and_flushes():
    window = make_window()
    solve_streaming(window, integer=False)
    epoch = EventEpoch(window, policy=FlushPolicy(max_events=2))
    ev1 = ClassArrival(lane=1, params=sample_class_params(
        jax.random.PRNGKey(5)))
    assert epoch.add(ev1) is False
    assert epoch.pending == (ev1,) and epoch.dirty_lanes == {1}
    assert not window.dirty.any()               # nothing applied yet
    ev2 = ClassDeparture(lane=2, slot=0)
    assert epoch.add(ev2) is True               # count trigger fires
    res = epoch.flush(integer=False)
    np.testing.assert_array_equal(res.resolved, [False, True, True, False])
    assert epoch.flushes == 1 and epoch.events_folded == 2
    assert len(epoch) == 0 and epoch.last_slots[0] is not None
    assert_equiv_cold(window, res)
    # an empty flush is legal and freezes every lane
    res2 = epoch.flush(integer=False)
    assert not res2.resolved.any()


def test_dirty_fraction_policy_flushes_early():
    window = make_window()
    epoch = EventEpoch(window, policy=FlushPolicy(
        max_events=None, max_dirty_fraction=0.5))
    assert epoch.add(ClassDeparture(lane=0, slot=0)) is False   # 1/4 dirty
    assert epoch.add(ClassDeparture(lane=0, slot=1)) is False   # still 1/4
    assert epoch.add(ClassDeparture(lane=3, slot=0)) is True    # 2/4 dirty


# --------------------------------------------------------------------------
# Deadline-aware FlushPolicy (SLA-critical events jump the coalescing queue)
# --------------------------------------------------------------------------

def hot_params(seed, E=-150.0):
    p = sample_class_params(jax.random.PRNGKey(seed))
    p["E"] = E
    return p


def test_deadline_policy_criticality_rules():
    window = make_window()
    pol = FlushPolicy.deadline(300.0, max_events=16)
    assert pol.deadline_slack_s == 300.0 and pol.flush_on_sla_tightening
    assert pol.max_events == 16
    # arrivals: critical iff the deadline is nearly exhausted
    assert pol.is_critical(
        ClassArrival(lane=0, params=hot_params(0)), window)
    assert not pol.is_critical(
        ClassArrival(lane=0, params=hot_params(1, E=-2000.0)), window)
    # SLA edits: tightening (E toward 0) is critical, relaxing is not,
    # non-deadline edits never are
    slot = window.occupied(1)[0]
    old_E = window._raw[(1, slot)]["E"]
    assert pol.is_critical(
        SLAEdit(lane=1, slot=slot, updates={"E": old_E + 50.0}), window)
    assert not pol.is_critical(
        SLAEdit(lane=1, slot=slot, updates={"E": old_E - 50.0}), window)
    assert not pol.is_critical(
        SLAEdit(lane=1, slot=slot, updates={"m": 12345.0}), window)
    # bulk kinds are never critical; plain policies have no deadline trigger
    assert not pol.is_critical(ClassDeparture(lane=1, slot=slot), window)
    assert not FlushPolicy().is_critical(
        ClassArrival(lane=0, params=hot_params(2)), window)
    # tightening=False keeps only the slack trigger
    lax = FlushPolicy.deadline(300.0, tightening=False)
    assert not lax.is_critical(
        SLAEdit(lane=1, slot=slot, updates={"E": old_E + 50.0}), window)
    assert lax.is_critical(
        SLAEdit(lane=1, slot=slot, updates={"E": -100.0}), window)
    # EventEpoch.add reports the critical flush demand
    epoch = EventEpoch(window, policy=pol)
    assert epoch.add(ClassArrival(lane=0, params=hot_params(3))) is True


def test_deadline_policy_session_flushes_critical_immediately():
    """Bulk events coalesce under the loose count bound; an SLA-critical
    event forces the flush at once, folding the buffered bulk events in."""
    eng = CapacityEngine(policies=Policies(
        flush=FlushPolicy.deadline(300.0, max_events=64),
        rounding=RoundingPolicy(False)))
    sess = eng.open_window(make_window())
    sess.solve()
    assert sess.apply(ClassArrival(
        lane=0, params=hot_params(10, E=-2000.0))) is None   # bulk: buffers
    assert sess.apply(ClassDeparture(
        lane=2, slot=sess.window.occupied(2)[0])) is None
    rep = sess.apply(ClassArrival(lane=1, params=hot_params(11)))
    assert rep is not None and not sess.pending
    np.testing.assert_array_equal(np.flatnonzero(rep.resolved), [0, 1, 2])
    assert_equiv_cold(sess.window, rep)


def test_solve_coalesced_deadline_policy_flush_boundaries():
    """A critical event mid-trace splits the epochs early; every flush still
    equals the cold solve of the window at that boundary."""
    window = make_window(n_max=9)
    solve_streaming(window, integer=False)
    slot = window.occupied(0)[0]
    tighten = SLAEdit(lane=0, slot=slot,
                      updates={"E": window._raw[(0, slot)]["E"] + 25.0})
    events = [
        ClassArrival(lane=2, params=hot_params(20, E=-1500.0)),
        ClassArrival(lane=3, params=hot_params(21, E=-1800.0)),
        tighten,                                 # critical -> flush of 3
        ClassArrival(lane=1, params=hot_params(22, E=-1600.0)),
    ]
    reports = list(solve_coalesced(
        window, events, policy=FlushPolicy.deadline(300.0, max_events=10),
        integer=False))
    assert len(reports) == 2                     # critical flush + trailing
    np.testing.assert_array_equal(np.flatnonzero(reports[0].resolved),
                                  [0, 2, 3])
    np.testing.assert_array_equal(np.flatnonzero(reports[1].resolved), [1])
    assert_equiv_cold(window, reports[-1])


# --------------------------------------------------------------------------
# Coalesced replay == per-event replay at flush boundaries
# --------------------------------------------------------------------------

@pytest.mark.parametrize("k", [3, 7])
def test_solve_coalesced_matches_per_event_at_boundaries(k):
    """Every flush of a coalesced replay equals the last per-event solve of
    its epoch (and hence the cold solve of the window at that point)."""
    w_co, w_ref = make_window(n_max=9), make_window(n_max=9)
    solve_streaming(w_co, integer=False)
    solve_streaming(w_ref, integer=False)
    trace = sample_event_trace(77, w_co, 20)
    boundary = 0
    for res in solve_coalesced(w_co, trace, policy=FlushPolicy(max_events=k),
                               integer=False):
        n_applied = min(boundary + k, len(trace))
        ref = None
        for ev in trace[boundary:n_applied]:     # per-event reference path
            w_ref.apply(ev)
            ref = solve_streaming(w_ref, integer=False)
        boundary = n_applied
        np.testing.assert_allclose(np.asarray(res.fractional.r),
                                   np.asarray(ref.fractional.r),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(res.iters),
                                      np.asarray(ref.iters))
        assert_equiv_cold(w_co, res)
    assert boundary == len(trace)                # trailing epoch flushed


def test_solve_coalesced_across_growth():
    """A coalesced epoch whose arrivals overflow n_max grows the window
    mid-epoch exactly like per-event application, and stays equivalent."""
    w = make_window(ns=(4, 5), n_max=5)
    solve_streaming(w, integer=False)
    events = [ClassArrival(lane=1, params=sample_class_params(
        jax.random.PRNGKey(60 + i))) for i in range(4)]
    results = list(solve_coalesced(w, events,
                                   policy=FlushPolicy(max_events=10),
                                   integer=False))
    assert len(results) == 1                     # one trailing flush
    assert w.n_max == 10                         # grew past 5
    np.testing.assert_array_equal(results[0].resolved, [False, True])
    assert_equiv_cold(w, results[0])


# --------------------------------------------------------------------------
# Dynamic lane count: add_lane / remove_lane
# --------------------------------------------------------------------------

def test_add_lane_freezes_existing_lanes():
    window = make_window()
    first = solve_streaming(window, integer=False)
    b = window.add_lane(sample_scenario(jax.random.PRNGKey(50), 4,
                                        capacity_factor=1.2))
    assert b == 4 and window.batch_size == 5
    res = solve_streaming(window, integer=False)
    np.testing.assert_array_equal(res.resolved, [False] * 4 + [True])
    for lane in range(4):                        # untouched lanes pass through
        np.testing.assert_array_equal(np.asarray(res.fractional.r[lane]),
                                      np.asarray(first.fractional.r[lane]))
    assert_equiv_cold(window, res)
    # the new lane is live: events address it like any other
    window.arrive(b, **sample_class_params(jax.random.PRNGKey(51)))
    assert_equiv_cold(window, solve_streaming(window, integer=False))


def test_add_empty_lane_and_validation():
    window = make_window(ns=(3, 4))
    solve_streaming(window, integer=False)
    with pytest.raises(ValueError):
        window.add_lane()                        # needs R= and rho_bar=
    b = window.add_lane(R=400.0, rho_bar=3.0)
    res = solve_streaming(window, integer=False)
    assert np.all(np.asarray(res.fractional.r[b]) == 0.0)
    assert bool(res.feasible[b])                 # empty lane trivially ok
    assert_equiv_cold(window, res)
    slot = window.arrive(b, **sample_class_params(jax.random.PRNGKey(8)))
    assert slot == 0
    assert_equiv_cold(window, solve_streaming(window, integer=False))


def test_add_lane_wider_than_window_grows_first():
    window = make_window(ns=(3,), n_max=4)
    solve_streaming(window, integer=False)
    wide = sample_scenario(jax.random.PRNGKey(9), 7, capacity_factor=1.2)
    b = window.add_lane(wide)
    assert window.n_max == 7 and window.n_classes[b] == 7
    assert_equiv_cold(window, solve_streaming(window, integer=False))


def test_remove_lane_shifts_and_freezes():
    window = make_window(ns=(5, 8, 3, 6))
    first = solve_streaming(window, integer=False)
    window.remove_lane(1)
    assert window.batch_size == 3
    res = solve_streaming(window, integer=False)
    assert not res.resolved.any()                # survivors stay frozen
    for new, old in enumerate((0, 2, 3)):
        np.testing.assert_array_equal(np.asarray(res.fractional.r[new]),
                                      np.asarray(first.fractional.r[old]))
    assert_equiv_cold(window, res)
    # raw book-keeping shifted: events address the post-shift lanes
    window.depart(1, window.occupied(1)[0])      # was lane 2 pre-removal
    res = solve_streaming(window, integer=False)
    np.testing.assert_array_equal(res.resolved, [False, True, False])
    assert_equiv_cold(window, res)
    window.remove_lane(2)
    window.remove_lane(1)
    with pytest.raises(ValueError):
        window.remove_lane(0)                    # never below one lane
    with pytest.raises(IndexError):
        window.remove_lane(5)


# --------------------------------------------------------------------------
# Compaction
# --------------------------------------------------------------------------

def sparsify(window, keep=2, lanes=None):
    """Depart all but ``keep`` classes per lane (lowest slots kept)."""
    for lane in (range(window.batch_size) if lanes is None else lanes):
        for slot in window.occupied(lane)[keep:]:
            window.depart(lane, slot)


def test_compact_keeps_clean_lanes_frozen():
    window = make_window(ns=(6, 7, 5, 6), n_max=12)
    sparsify(window, keep=2)
    pre = solve_streaming(window, integer=False)
    pre_occ = [window.occupied(b) for b in range(4)]
    slot_map = window.compact()
    assert window.n_max == 2 and window.occupancy == 1.0
    # stored equilibrium was remapped -> nothing re-iterates, values move
    post = solve_streaming(window, integer=False)
    assert not post.resolved.any()
    for b in range(4):
        np.testing.assert_array_equal(
            np.asarray(post.fractional.r[b]),
            np.asarray(pre.fractional.r[b])[pre_occ[b]])
        for old_slot, new_slot in zip(pre_occ[b], range(len(pre_occ[b]))):
            assert slot_map[b, old_slot] == new_slot
    assert_equiv_cold(window, post)
    # dirtying one lane after compaction re-solves it on the packed layout
    window.arrive(2, **sample_class_params(jax.random.PRNGKey(21)))
    res = solve_streaming(window, integer=False)
    np.testing.assert_array_equal(res.resolved, [False, False, True, False])
    assert_equiv_cold(window, res)


def test_compact_width_validation_and_headroom():
    window = make_window(ns=(4, 2))
    solve_streaming(window, integer=False)
    with pytest.raises(ValueError):
        window.compact(n_max=3)                  # below the widest lane
    slot_map = window.compact(n_max=6)           # explicit headroom
    assert window.n_max == 6
    assert (slot_map >= -1).all()
    res = solve_streaming(window, integer=False)
    assert not res.resolved.any()
    assert_equiv_cold(window, res)
    # idempotent fast path: already packed at this width
    again = window.compact(n_max=6)
    np.testing.assert_array_equal(again[:, :4],
                                  np.where(window._mask[:, :4],
                                           np.arange(4)[None, :], -1))


def test_compact_preserves_baseline_memo():
    window = make_window(cf=0.95)
    res = solve_streaming(window, integer=False, cross_check=True)
    gaps = np.asarray(res.centralized_gap).copy()
    totals = window.baseline_totals.copy()
    sparsify(window, keep=2, lanes=[1])
    solve_streaming(window, integer=False, cross_check=True)
    totals[1] = window.baseline_totals[1]
    window.compact()
    assert not window.baseline_stale.any()       # memo survives the re-layout
    res2 = solve_streaming(window, integer=False, cross_check=True)
    np.testing.assert_array_equal(window.baseline_totals, totals)
    assert np.all(np.asarray(res2.centralized_gap) >= -1e-9)
    del gaps


# --------------------------------------------------------------------------
# Mesh composition: shrink -> regrow -> compact (the PR 3 untested corner)
# --------------------------------------------------------------------------

@needs_devices
def test_shrink_then_regrow_under_mesh():
    """Lane removal below the device multiple, re-growth past it, and
    compaction all compose with the sharded streaming path."""
    mesh = lane_mesh()
    w_mesh, w_ref = make_window(ns=(5, 8, 3, 6, 4, 7)), \
        make_window(ns=(5, 8, 3, 6, 4, 7))
    solve_streaming(w_mesh, integer=False, mesh=mesh)
    solve_streaming(w_ref, integer=False)

    for lane in (4, 1, 0):                       # shrink 6 -> 3 lanes
        w_mesh.remove_lane(lane)
        w_ref.remove_lane(lane)
    res_m = solve_streaming(w_mesh, integer=False, mesh=mesh)
    res_r = solve_streaming(w_ref, integer=False)
    assert not res_m.resolved.any()
    np.testing.assert_allclose(np.asarray(res_m.fractional.r),
                               np.asarray(res_r.fractional.r),
                               rtol=1e-6, atol=1e-6)

    for i in range(2):                           # regrow 3 -> 5 lanes
        scn = sample_scenario(jax.random.PRNGKey(70 + i), 4 + i,
                              capacity_factor=1.2)
        w_mesh.add_lane(scn)
        w_ref.add_lane(scn)
    sparsify(w_mesh, keep=2)
    sparsify(w_ref, keep=2)
    sm_mesh = w_mesh.compact()
    sm_ref = w_ref.compact()
    np.testing.assert_array_equal(sm_mesh, sm_ref)
    res_m = solve_streaming(w_mesh, integer=False, mesh=mesh)
    res_r = solve_streaming(w_ref, integer=False)
    np.testing.assert_array_equal(res_m.resolved, res_r.resolved)
    np.testing.assert_allclose(np.asarray(res_m.fractional.r),
                               np.asarray(res_r.fractional.r),
                               rtol=1e-6, atol=1e-6)
    assert_equiv_cold(w_mesh, res_m)


@needs_devices
def test_coalesced_random_trace_under_mesh_with_compaction():
    """The acceptance criterion: a coalesced replay of a random trace under
    a mesh — across growth and a compaction at a flush boundary — equals the
    cold solve of the window at every flush."""
    mesh = lane_mesh()
    window = make_window(n_max=9)
    solve_streaming(window, integer=False, mesh=mesh)
    trace = sample_event_trace(123, window, 18)
    for res in solve_coalesced(window, trace, policy=FlushPolicy(max_events=6),
                               integer=False, mesh=mesh):
        assert_equiv_cold(window, res)
    window.compact()                             # flush boundary re-layout
    res = solve_streaming(window, integer=False, mesh=mesh)
    assert not res.resolved.any()
    assert_equiv_cold(window, res)
    # traces sampled post-compaction keep streaming on the packed layout
    trace2 = sample_event_trace(124, window, 8)
    for res in solve_coalesced(window, trace2,
                               policy=FlushPolicy(max_events=4),
                               integer=False, mesh=mesh):
        assert_equiv_cold(window, res)


# --------------------------------------------------------------------------
# Fleet integration: clusters joining/leaving + compaction policy
# --------------------------------------------------------------------------

def test_epoch_stream_fleet_arrive_depart_and_compaction():
    from repro.cluster import FleetSimulator, TenantSpec, epoch_stream

    def tenants(k, start=0):
        return [TenantSpec(f"t{start + i}", "x", "train_4k",
                           deadline_s=100.0 + 7.0 * (start + i),
                           H_up=10 + (start + i), H_low=4,
                           penalty_per_job=20000.0 + 500.0 * (start + i))
                for i in range(k)]

    profiles = {f"t{i}": (1.0 + 0.2 * i, 0.5, 1.0) for i in range(10)}
    mk = lambda chips, k, start=0: FleetSimulator(
        total_chips=chips, tenants=tenants(k, start=start))
    streamed = [mk(800, 4), mk(1200, 5)]
    for f in streamed:
        f._profiles = dict(profiles)
    newcomer_fleet = mk(600, 2, start=7)
    newcomer_fleet._profiles = dict(profiles)

    epochs = [
        [],
        [("fleet-arrive", newcomer_fleet),
         ("arrive", 2, tenants(1, start=9)[0])],  # event lands in new lane
        [("fleet-depart", 0),                     # indices shift down
         ("depart", 0, "t1"), ("depart", 0, "t2"), ("depart", 0, "t3"),
         ("capacity", 1, 500)],
    ]
    got = list(epoch_stream(streamed, epochs, compact_below=0.6))
    assert [len(a) for a in got] == [2, 3, 2]

    # end state: fleet 0 == original fleet 1 shrunk, fleet 1 == newcomer + t9
    fresh0 = mk(1200, 5)
    fresh0.tenants = [t for t in fresh0.tenants
                      if t.name not in ("t1", "t2", "t3")]
    fresh1 = mk(500, 2, start=7)
    fresh1.tenants.append(tenants(1, start=9)[0])
    for f in (fresh0, fresh1):
        f._profiles = dict(profiles)
    want0, want1 = fresh0.epoch(), fresh1.epoch()
    assert got[-1][0].chips == want0.chips and got[-1][0].h == want0.h
    assert got[-1][1].chips == want1.chips and got[-1][1].h == want1.h
    assert got[-1][0].total_cost == pytest.approx(want0.total_cost, rel=1e-6)
    assert got[-1][1].total_cost == pytest.approx(want1.total_cost, rel=1e-6)
