"""The window's one write path: ``AdmissionWindow.apply_epoch``.

Every epoch, one event or many, is packed on the host, sent in one upload
(``streaming._upload``) and committed by one compiled program
(``streaming._epoch_commit``) that derives the staged classes' constants on
the device.  Since ``apply`` wraps ``apply_epoch``, comparing the two
(``tests/test_coalescing.py``) compares the path with itself; these tests
hold it to independent oracles instead:

* after random traces of all four event kinds (growth past ``n_max``
  included), every per-class leaf equals ``jax.jit(types.derive)`` of the
  slot's raw parameters where the trace admitted or edited a class, the
  values the window was built with where it did not, and the neutral values
  where no class is, and ``rho_hat``, ``R``, the mask, the raw registry and the
  stored allocation equal a plain host model of the same events — exactly,
  in the host layout and resident on a 4-device CPU mesh;
* the path's cost: a second event of each kind compiles nothing, and an
  epoch makes one upload, runs one program and reads nothing back.
"""
import jax
import numpy as np
import pytest
from jax._src import array as jax_array
from jax._src import core as jax_core

from repro.core import (AdmissionWindow, CapacityChange, CapacityEngine,
                        ClassArrival, ClassDeparture, Policies,
                        RoundingPolicy, SLAEdit, SolverConfig, lane_mesh,
                        sample_class_params, sample_event_trace,
                        sample_scenario, streaming)
from repro.core.streaming import grown_n_max
from repro.core.types import RAW_CLASS_FIELDS, derive, neutral_class_values

D = jax.device_count()
MESH_D = min(4, D)
LAYOUTS = ["host",
           pytest.param("resident", marks=pytest.mark.skipif(
               D < 2, reason="needs >= 2 devices (conftest forces 8 on CPU)"))]
CLASS_FIELDS = tuple(neutral_class_values(0.0))


def make_window(seed, *, ns=(4, 6, 2, 5, 3), n_max=7):
    scns = [sample_scenario(jax.random.PRNGKey(seed + i), n,
                            capacity_factor=1.3) for i, n in enumerate(ns)]
    return AdmissionWindow(scns, n_max=n_max)


def open_session(window, layout):
    """A session over ``window``, solved once so it holds a stored state."""
    cfg = (SolverConfig(mesh=lane_mesh(MESH_D), residency="resident")
           if layout == "resident" else SolverConfig())
    session = CapacityEngine(
        cfg, Policies(rounding=RoundingPolicy(False))).open_window(window)
    session.solve()
    return session


class HostModel:
    """The events' effect as plain host bookkeeping: lowest free slot per
    arrival (the window's growth schedule when a row is full), merged SLA
    edits, capacities, and the slots whose stored allocation an epoch
    zeroes (net vacated in that epoch)."""

    def __init__(self, window):
        self.mask = window._mask.copy()
        self.raw = {k: dict(v) for k, v in window._raw.items()}
        self.R = np.asarray(window.batch.scenarios.R).copy()
        self.r = np.asarray(window.state.r)[:window.batch_size].copy()
        self.touched, self.written = set(), set()
        self.growth = window.growth_factor
        self.built = {f: np.asarray(getattr(window.batch.scenarios, f))
                      for f in CLASS_FIELDS}

    def epoch(self, events):
        net = {}
        for ev in events:
            if isinstance(ev, ClassArrival):
                free = np.flatnonzero(~self.mask[ev.lane])
                if free.size == 0:
                    pad = grown_n_max(self.mask.shape[1], self.growth) \
                        - self.mask.shape[1]
                    self.mask = np.pad(self.mask, ((0, 0), (0, pad)))
                    self.r = np.pad(self.r, ((0, 0), (0, pad)))
                    free = np.flatnonzero(~self.mask[ev.lane])
                key = (ev.lane, int(free[0]))
                self.mask[key] = True
                self.raw[key] = dict(ev.params)
                net[key] = True
            elif isinstance(ev, ClassDeparture):
                key = (ev.lane, ev.slot)
                self.mask[key] = False
                del self.raw[key]
                net[key] = False
            elif isinstance(ev, SLAEdit):
                key = (ev.lane, ev.slot)
                self.raw[key] = {**self.raw[key], **ev.updates}
                net[key] = True
            else:
                self.R[ev.lane] = ev.R
                continue
            self.touched.add(ev.lane)
            self.written.add(key)
        for key, occupied in net.items():
            if not occupied:
                self.r[key] = 0.0


def assert_matches_model(window, model):
    scn = window.batch.scenarios
    dt = scn.A.dtype
    rho_bar = np.asarray(scn.rho_bar)
    np.testing.assert_array_equal(window._mask, model.mask)
    assert window._raw == model.raw
    keys = sorted(model.raw)
    new = [k for k in keys if k in model.written]
    want = jax.jit(derive)(
        **{f: np.asarray([model.raw[k][f] for k in new], dt)
           for f in RAW_CLASS_FIELDS}, R=0.0, rho_bar=0.0)
    lanes, slots = np.asarray(new).reshape(-1, 2).T
    old_lanes, old_slots = np.asarray(
        [k for k in keys if k not in model.written]).reshape(-1, 2).T
    for f in CLASS_FIELDS:
        leaf = np.asarray(getattr(scn, f))
        np.testing.assert_array_equal(leaf[lanes, slots],
                                      np.asarray(getattr(want, f)), f)
        # classes the trace never wrote keep the values they were built with
        np.testing.assert_array_equal(leaf[old_lanes, old_slots],
                                      model.built[f][old_lanes, old_slots], f)
        vacant = ~model.mask
        neutral = (np.broadcast_to(rho_bar[:, None], leaf.shape)
                   if f == "rho_up"
                   else np.full(leaf.shape, neutral_class_values(0.0)[f]))
        np.testing.assert_array_equal(leaf[vacant],
                                      neutral[vacant].astype(dt), f)
    rho_up = np.asarray(scn.rho_up)
    for lane in range(window.batch_size):
        if lane in model.touched:
            hat = np.max(np.where(model.mask[lane], rho_up[lane],
                                  rho_bar[lane]))
        else:          # never re-derived: the value the lane was built with
            hat = max(model.raw[k]["rho_up"] for k in keys if k[0] == lane)
        assert np.asarray(scn.rho_hat)[lane] == np.asarray(hat, dt), lane
    np.testing.assert_array_equal(np.asarray(scn.R), model.R.astype(dt))
    r = np.asarray(window.state.r)[:window.batch_size]
    np.testing.assert_array_equal(r, model.r.astype(dt))
    if window.is_resident:
        mirror = np.asarray(window._mask_dev)[:window.batch_size]
        np.testing.assert_array_equal(mirror, model.mask)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("seed", [0, 5])
def test_write_path_matches_derive_oracle(layout, seed):
    """Random traces of all four kinds, folded in epochs of 1 to 9 events
    (one-event epochs through ``apply``), grow the window past ``n_max``
    and leave every leaf where the oracles put it, bit for bit."""
    window = make_window(seed)
    session = open_session(window, layout)
    model = HostModel(window)
    trace = sample_event_trace(100 + seed, window, 60, p_arrive=0.5,
                               p_depart=0.25, p_edit=0.15, p_capacity=0.1)
    rng = np.random.default_rng(seed)
    at = 0
    while at < len(trace):
        epoch = trace[at:at + int(rng.integers(1, 10))]
        at += len(epoch)
        if len(epoch) == 1:
            window.apply(epoch[0])
        else:
            window.apply_epoch(epoch)
        model.epoch(epoch)
        assert_matches_model(window, model)
    assert window.n_max > 7                  # the trace grew the window
    assert window.is_resident == (layout == "resident")
    assert session.window is window


def one_of_each(window, seed):
    """One event of each kind against ``window`` as it stands: an arrival
    into the emptiest lane (so the window does not grow), a departure and
    an SLA edit, and a capacity change."""
    occupied = np.argwhere(window._mask)
    gone, edited = occupied[0], occupied[-1]
    lane = int(np.argmin(window.n_classes))
    return [ClassArrival(lane=lane, params=sample_class_params(
                jax.random.PRNGKey(seed))),
            ClassDeparture(lane=int(gone[0]), slot=int(gone[1])),
            SLAEdit(lane=int(edited[0]), slot=int(edited[1]),
                    updates={"E": -900.0 - seed, "m": 20.0 + seed}),
            CapacityChange(lane=2, R=50.0 + seed)]


class Spies:
    """Counts, over the write path, uploads, commit programs, eager
    primitives, device reads and backend compiles."""

    def __init__(self, monkeypatch):
        self.uploads = self.commits = self.reads = self.compiles = 0
        self.eager = []
        real_upload, real_commit = streaming._upload, streaming._epoch_commit

        def upload(operands):
            self.uploads += 1
            return real_upload(operands)

        def commit(*args):
            self.commits += 1
            return real_commit(*args)

        monkeypatch.setattr(streaming, "_upload", upload)
        monkeypatch.setattr(streaming, "_epoch_commit", commit)
        # a host read of a device array goes through one of these three
        # (float/item/tolist -> _value; np.asarray -> the buffer protocol;
        # jax.device_get -> __array__), whether or not it copies
        for name in ("_value", "__buffer__", "__array__"):
            real = jax_array.ArrayImpl.__dict__[name]
            if isinstance(real, property):
                spy = property(lambda arr, real=real: self._read(real.fget,
                                                                 arr))
            else:
                def spy(arr, *a, real=real, **kw):
                    return self._read(real, arr, *a, **kw)
            monkeypatch.setattr(jax_array.ArrayImpl, name, spy)
        real_bind = jax_core.EvalTrace.process_primitive

        def bind(trace, prim, args, params):
            self.eager.append(prim.name)
            return real_bind(trace, prim, args, params)

        monkeypatch.setattr(jax_core.EvalTrace, "process_primitive", bind)
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        self.real_commit = real_commit

    def _read(self, fn, arr, *a, **kw):
        self.reads += 1
        return fn(arr, *a, **kw)

    def _compiled(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._compiled)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_second_event_of_each_kind_compiles_nothing_and_reads_nothing(
        layout, monkeypatch):
    """After one event of each kind, a second of each kind compiles
    nothing, and each event costs exactly one upload and one commit
    program: no eager operation, no read of a device array."""
    window = make_window(3)
    open_session(window, layout)
    for ev in one_of_each(window, 0):
        window.apply(ev)
    events = one_of_each(window, 1)
    spies = Spies(monkeypatch)
    cached = spies.real_commit._cache_size()
    try:
        for ev in events:
            window.apply(ev)
    finally:
        spies.close()
    assert spies.compiles == 0
    assert spies.real_commit._cache_size() == cached
    assert spies.uploads == spies.commits == len(events)
    assert spies.eager == [] and spies.reads == 0


def test_many_event_epoch_is_one_upload_and_one_program(monkeypatch):
    """An epoch of many events of every kind is still one upload and one
    commit program, with nothing read back."""
    window = make_window(4)
    open_session(window, "host")
    trace = sample_event_trace(9, window, 12)
    spies = Spies(monkeypatch)
    try:
        window.apply_epoch(trace)
    finally:
        spies.close()
    assert spies.uploads == spies.commits == 1
    assert spies.eager == [] and spies.reads == 0
