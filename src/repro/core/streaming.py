"""Streaming admission engine — the paper's *runtime* allocation loop.

The paper's whole point (Sec. 1, Sec. 5 "runtime" experiments) is that the
Resource Manager and Class Managers re-negotiate capacity **as job classes
arrive and leave**, not on a fixed batch.  This module turns the batched GNEP
engine (``game.solve_distributed_batch``) into that runtime system:

* :class:`AdmissionWindow` maintains a *live* padded :class:`ScenarioBatch`
  under :class:`~repro.core.types.ClassArrival` /
  :class:`~repro.core.types.ClassDeparture` /
  :class:`~repro.core.types.SLAEdit` /
  :class:`~repro.core.types.CapacityChange` events.  A departing class's slot
  is refilled with solver-inert neutral values and recycled by the next
  arrival (free-slot recycling in the mask); the window repads every leaf to
  a larger ``n_max`` only when a lane's row is actually full, so steady-state
  event application never re-stacks the batch and never changes XLA shapes
  (no recompilation).

* :meth:`AdmissionWindow.warm_start` builds the incremental re-solve init:
  lanes whose scenario is unchanged since their last equilibrium are
  *frozen* (zero solver iterations — their stored equilibrium passes through
  the vmapped while-loop untouched), and only *dirty* lanes iterate.  Dirty
  lanes restart from the paper's cold Algorithm 4.1 init so they reproduce
  the cold trajectory exactly: CM bids only escalate during the game, so
  carrying converged bids across a scenario change would steer the game to a
  different (higher-price) equilibrium.  This makes the streaming solve
  numerically equivalent to a cold re-solve of the final window while doing
  only the dirty lanes' work.

* Windows are *dynamic*: :meth:`AdmissionWindow.apply_epoch` folds any
  number of events, one included, into one atomic update (one upload of
  host-packed operands and one compiled commit program that derives, writes
  and refreshes on the device, with no read back); :class:`EventEpoch` +
  :class:`FlushPolicy` decide *when* to re-solve (count / dirty-fraction
  triggers); lanes can be added and removed between solves
  (:meth:`AdmissionWindow.add_lane` / :meth:`AdmissionWindow.remove_lane`);
  and :meth:`AdmissionWindow.compact` re-packs sparse long-lived windows,
  remapping the stored equilibrium so frozen lanes stay frozen across the
  re-layout.

The user-facing layer is :class:`repro.core.engine.CapacityEngine` /
:class:`repro.core.engine.WindowSession` (``open_window`` -> ``apply`` /
``flush`` / ``stream``: warm solve + Algorithm 4.2 rounding + optional
centralized cross-check, flush cadence and compaction as policies); the
deprecated ``repro.core.allocator.solve_streaming`` / ``solve_coalesced``
shims delegate there.  :func:`sample_event_trace` generates
random-but-replayable event traces for tests and
``benchmarks/streaming_perf.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import game, sharding
from repro.core.profiles import sample_class_params
from repro.core.types import (RAW_CLASS_FIELDS, CapacityChange, ClassArrival,
                              ClassDeparture, Scenario, ScenarioBatch,
                              SLAEdit, StreamEvent, WindowState, derive,
                              neutral_class_values, pad_scenario,
                              stack_scenarios)

#: Per-class Scenario fields (raw + derived) written on every class write.
_CLASS_FIELDS = tuple(neutral_class_values(0.0).keys())
#: Raw values of a vacated slot's row in an epoch's staged rows: finite
#: inputs for the in-program derive, whose result the slot never keeps.
_NEUTRAL_RAW = tuple(neutral_class_values(1.0)[f] for f in RAW_CLASS_FIELDS)


def _pad_idx(idx: list) -> list:
    """Pad a scatter-index list to the next power of two by repeating its
    last entry.  Scattering the same value to a duplicated index is
    idempotent, and the bucketed shapes bound how many signatures
    :func:`_epoch_commit` ever compiles — epochs of any size hit a warm
    compile cache after the first few flushes."""
    if not idx:
        return idx
    return idx + [idx[-1]] * ((1 << (len(idx) - 1).bit_length()) - len(idx))


def _upload(operands):
    """Send one epoch's host-packed operands to the device in ONE call.

    The only host->device transfer of the write path: every array of the
    epoch rides one ``jax.device_put`` of the operand tree, where a
    ``jnp.asarray`` per field would pay a PJRT transfer and allocation
    each.
    """
    return jax.device_put(operands)


@jax.jit
def _epoch_commit(scn: Scenario, mask_dev, state_r, classes, capacity):
    """The WHOLE epoch commit as one jitted program: derive of the staged
    classes, class-field scatter, resident-mask-mirror scatter,
    vacated-slot warm-state zeroing, lane capacity updates and the
    rho_hat refresh.

    ``classes`` is ``(raw, idx, rows)``: the staged slots' raw parameters
    as (K, 9) rows in :data:`RAW_CLASS_FIELDS` order, their
    ``(lane, slot, occupied)`` as (K, 3) ints, and each staged slot's
    lane row of the host mask after the epoch (K, n_max).  Occupied slots
    take :func:`~repro.core.types.derive` of their row, vacated slots the
    neutral values with ``rho_up`` = their lane's ``rho_bar``.
    ``capacity`` is ``(lanes, R)``.  Absent parts are ``None`` and prune
    their branch at trace time (each presence combination compiles once).
    Nothing is read back: the host keeps the raw parameters and the mask.
    """
    if classes is not None:
        raw, idx, rows = classes
        li, si, occ = idx[:, 0], idx[:, 1], idx[:, 2] != 0
        derived = derive(*raw.T, R=0.0, rho_bar=0.0)
        neutral = neutral_class_values(0.0)
        neutral["rho_up"] = scn.rho_bar[li]
        scn = scn.replace(**{
            f: getattr(scn, f).at[li, si].set(
                jnp.where(occ, getattr(derived, f), neutral[f]))
            for f in _CLASS_FIELDS})
        if mask_dev is not None:
            mask_dev = mask_dev.at[li, si].set(occ)
        if state_r is not None:
            # vacated slots restart from 0; occupied staged slots keep their
            # stored allocation (their lane goes dirty and restarts cold
            # anyway)
            state_r = state_r.at[li, si].set(
                jnp.where(occ, state_r[li, si], jnp.zeros((), state_r.dtype)))
        # rho_hat = max_i rho_up over ADMITTED classes (paper (P5e) interval
        # end); an empty lane degenerates to the single candidate rho_bar.
        # Rows of one lane are equal, so duplicate lanes write one value.
        hats = jnp.max(jnp.where(rows, scn.rho_up[li],
                                 scn.rho_bar[li][:, None]), axis=1)
        scn = scn.replace(rho_hat=scn.rho_hat.at[li].set(hats))
    if capacity is not None:
        lanes, R = capacity
        scn = scn.replace(R=scn.R.at[lanes].set(R))
    return scn, mask_dev, state_r


class AdmissionWindow:
    """A live, padded :class:`ScenarioBatch` plus last-equilibrium state.

    Each *lane* is one running allocation game (one cluster / fleet); events
    admit, remove or renegotiate job classes inside a lane.  The window keeps

    * the stacked :class:`Scenario` leaves ((B, n_max) per class, (B,)
      scalars) with vacated / never-used slots held at solver-inert neutral
      values (:func:`~repro.core.types.neutral_class_values`);
    * a host-side occupancy mask mirroring ``ScenarioBatch.mask`` (kept on
      host so event application never synchronises with the device);
    * the previous equilibrium (:class:`~repro.core.types.WindowState`) and a
      per-lane *dirty* flag driving the warm-started incremental re-solve.

    Parameters
    ----------
    scenarios : Sequence[Scenario]
        Initial (possibly ragged) instances, one per lane.  Neither the lane
        count B nor the class counts are fixed: lanes grow/shrink between
        solves via :meth:`add_lane` / :meth:`remove_lane`, and sparse
        windows re-pack via :meth:`compact`.
    n_max : int, optional
        Initial padded width.  Defaults to the largest initial class count;
        give headroom to avoid early growth repads.
    growth_factor : float, optional
        When a lane's row is full, every leaf is repadded to
        ``max(ceil(growth_factor * n_max), n_max + 1)`` columns.  Stored
        equilibria stay valid across growth because padding is inert.

    Notes
    -----
    Feasibility is intentionally *not* enforced at admission time: a burst of
    arrivals may legitimately push ``sum(r_low) > R`` until the operator
    sheds load or adds capacity, so infeasible transients must be
    representable.  ``solve_streaming`` reports per-lane ``feasible`` flags.
    """

    def __init__(self, scenarios: Sequence[Scenario], *,
                 n_max: Optional[int] = None, growth_factor: float = 2.0):
        scns = list(scenarios)
        if not scns:
            raise ValueError("AdmissionWindow needs at least one lane")
        if growth_factor <= 1.0:
            raise ValueError("growth_factor must be > 1")
        batch = stack_scenarios(scns, n_max=n_max)
        self._scn = batch.scenarios
        self._mask = np.asarray(batch.mask).copy()
        # device-residency state (None = classic host-round-trip layout):
        # when resident, _scn/_state leaves are lane-padded to the mesh
        # multiple and placed with lane_sharding; _mask_dev mirrors _mask
        # on the mesh so flushes never upload the occupancy mask.
        self._resident_mesh = None
        self._mask_dev = None
        self._n_classes_dev = self._n_classes_host = None
        self.growth_factor = float(growth_factor)
        self.dirty = np.zeros(self.batch_size, bool)
        # per-lane memo of the exact centralized (P3) total, invalidated by
        # the same events that dirty a lane (solve_streaming's cross-check
        # recomputes only stale lanes instead of the whole batch per event)
        self.baseline_totals = np.full(self.batch_size, np.nan)
        self.baseline_stale = np.ones(self.batch_size, bool)
        self._state: Optional[WindowState] = None
        # raw per-class params so SLAEdit can merge partial updates
        # (one device->host transfer per field per lane, not per scalar)
        self._raw: Dict[Tuple[int, int], dict] = {}
        for b, s in enumerate(scns):
            cols = {f: np.asarray(getattr(s, f)) for f in RAW_CLASS_FIELDS}
            for i in range(s.n):
                self._raw[(b, i)] = {f: float(cols[f][i])
                                     for f in RAW_CLASS_FIELDS}

    # ------------------------------------------------------------------ views
    @property
    def batch_size(self) -> int:
        return self._mask.shape[0]

    @property
    def n_max(self) -> int:
        return self._mask.shape[1]

    @property
    def n_classes(self) -> np.ndarray:
        """(B,) host array — current number of admitted classes per lane."""
        return self._mask.sum(axis=1)

    @property
    def batch(self) -> ScenarioBatch:
        """The current window as a solver-ready :class:`ScenarioBatch`."""
        # NB: the mask must be snapshotted — jnp.asarray zero-copies an
        # aligned numpy buffer on CPU, which would hand the solver (and
        # every report holding this batch) a live view of ``_mask`` that
        # later in-place event applications silently rewrite.
        scn = self._scn
        b = self.batch_size
        if int(scn.A.shape[0]) > b:
            # resident layout carries inert mesh-padding lanes; the host
            # mirror materialized here is always the logical window
            scn = jax.tree_util.tree_map(lambda leaf: leaf[:b], scn)
        return ScenarioBatch(scenarios=scn,
                             mask=jnp.asarray(self._mask.copy()),
                             n_classes=jnp.asarray(self.n_classes))

    @property
    def state(self) -> Optional[WindowState]:
        """Last committed equilibrium, or None before the first solve."""
        return self._state

    @property
    def occupancy(self) -> float:
        """Fraction of the (B, n_max) slot grid holding an admitted class.

        The compaction signal: a long-lived window whose tenants churn
        drifts toward a sparse mask (occupancy well below 1), paying solver
        work proportional to ``n_max`` for classes that are long gone —
        :meth:`compact` re-packs it.
        """
        return float(self._mask.mean()) if self._mask.size else 0.0

    def occupied(self, lane: int) -> List[int]:
        """Slot indices currently holding an admitted class in ``lane``."""
        return [int(i) for i in np.flatnonzero(self._mask[lane])]

    # -------------------------------------------------------- device residency
    @property
    def is_resident(self) -> bool:
        """Whether the window's device leaves live lane-sharded on a mesh."""
        return self._resident_mesh is not None

    @property
    def resident_mesh(self):
        """The 1-D lane mesh the window is resident on (None when not)."""
        return self._resident_mesh

    def make_resident(self, mesh) -> None:
        """Place the window's device state lane-sharded on ``mesh``, to stay.

        After this, the scenario leaves, the occupancy-mask mirror and the
        stored equilibrium are lane-padded to the mesh's device multiple
        (inert padding, exactly :func:`repro.core.sharding.pad_batch_lanes`)
        and committed with ``lane_sharding`` — and every subsequent event
        scatter writes *into* the resident arrays (XLA sharding propagation
        keeps them lane-sharded), so flushes pay zero per-solve host->mesh
        resharding.  Geometry changes (:meth:`add_lane`,
        :meth:`remove_lane`, :meth:`compact`) drop to the logical host
        layout internally and re-establish residency before returning;
        :meth:`grow` re-places in-place.  The host occupancy mask and raw
        parameter book-keeping stay authoritative on the host throughout.

        Parameters
        ----------
        mesh : jax.sharding.Mesh
            1-D lane mesh (``repro.core.sharding.lane_mesh``).  Re-calling
            with a different mesh migrates the window.
        """
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"lane residency needs a 1-D mesh, got axes {mesh.axis_names}")
        if self._resident_mesh is not None and self._resident_mesh != mesh:
            self._exit_residency()
        self._resident_mesh = mesh
        self._place_device_leaves()

    def release_resident(self) -> None:
        """Return to the classic host-round-trip layout.

        Trims the mesh-padding lanes off every device leaf, gathers the
        leaves back to the default device and drops the device mask mirror;
        the window is afterwards indistinguishable from one that was never
        resident (``tests/test_resident.py`` round-trips through this).
        """
        if self._resident_mesh is not None:
            self._exit_residency()

    def resident_batch(self) -> ScenarioBatch:
        """The resident (lane-padded, mesh-placed) solver view of the window.

        Unlike :attr:`batch` this materializes NO host mirror: scenarios and
        mask are the live resident arrays (padded lane count), and only the
        tiny (padded B,) class-count vector is uploaded per call.

        Returns
        -------
        ScenarioBatch
            Leaves carry the PADDED lane count; padding lanes are inert.
        """
        if self._resident_mesh is None:
            raise RuntimeError(
                "window is not device-resident — call make_resident(mesh)")
        pad_b = int(self._mask_dev.shape[0])
        counts = np.zeros(pad_b, np.int64)
        counts[:self.batch_size] = self.n_classes
        # the solver is mask-driven (game.py never reads n_classes), so the
        # counts vector is report surface only — cache its device copy and
        # re-upload only when occupancy actually changed
        if (self._n_classes_dev is None
                or not np.array_equal(counts, self._n_classes_host)):
            self._n_classes_dev = jax.device_put(
                jnp.asarray(counts),
                sharding.lane_sharding(self._resident_mesh))
            self._n_classes_host = counts
        return ScenarioBatch(scenarios=self._scn, mask=self._mask_dev,
                             n_classes=self._n_classes_dev)

    def resident_warm_start(self, rbatch: ScenarioBatch):
        """On-device incremental-re-solve init for the resident solve path.

        The resident analog of :meth:`warm_start` + ``pad_warm_start``:
        frozen/dirty splitting happens in one jitted program over the padded
        resident leaves (``sharding.resident_warm_init``), and the returned
        init's buffers are fresh — ``sharding.solve_resident_batch`` donates
        them.  Only the (padded B,) dirty-flag vector is uploaded.

        Parameters
        ----------
        rbatch : ScenarioBatch
            The window's :meth:`resident_batch` (passed in so one flush
            builds it exactly once).

        Returns
        -------
        (game.BatchWarmStart, np.ndarray)
            The donation-safe padded init, and the (B,) host ``resolved``
            flags (lanes that will iterate — dirty or never-solved).
        """
        if self._resident_mesh is None:
            raise RuntimeError(
                "window is not device-resident — call make_resident(mesh)")
        if self._state is None:
            return (sharding.resident_cold_init(rbatch),
                    np.ones(self.batch_size, bool))
        pad_b = rbatch.batch_size
        dirty_full = np.zeros(pad_b, bool)
        dirty_full[:self.batch_size] = self.dirty
        dirty_dev = jax.device_put(
            jnp.asarray(dirty_full),
            sharding.lane_sharding(self._resident_mesh))
        init = sharding.resident_warm_init(rbatch, self._state, dirty_dev)
        # active == dirty here: a never-solved lane is always dirty (the
        # only path creating solved=False rows, add_lane, also dirties)
        return init, self.dirty.copy()

    def _place_device_leaves(self) -> None:
        """(Re-)establish the resident placement: pad the lane axis to the
        mesh multiple when needed, device_put every leaf with lane
        sharding, rebuild the device mask mirror from the host mask."""
        mesh = self._resident_mesh
        B, n_max = self.batch_size, self.n_max
        pad_b = sharding.padded_lane_count(B, mesh.devices.size)
        sh = sharding.lane_sharding(mesh)
        rows = int(self._scn.A.shape[0])
        if rows == B and pad_b > B:
            host = ScenarioBatch(scenarios=self._scn,
                                 mask=jnp.asarray(self._mask.copy()),
                                 n_classes=jnp.asarray(self.n_classes))
            self._scn = sharding.pad_batch_lanes(host, pad_b).scenarios
        elif rows not in (B, pad_b):
            raise AssertionError(
                f"resident lane-axis invariant broken: {rows} device rows, "
                f"B={B}, padded={pad_b}")
        self._scn = jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, sh), self._scn)
        full = np.zeros((pad_b, n_max), bool)
        full[:B] = self._mask
        self._mask_dev = jax.device_put(jnp.asarray(full), sh)
        self._n_classes_dev = self._n_classes_host = None
        if self._state is not None:
            self._state = jax.tree_util.tree_map(
                lambda leaf: jax.device_put(leaf, sh),
                sharding.pad_window_state(self._state, pad_b))

    def _exit_residency(self) -> None:
        """Materialize the logical host layout: trim mesh-padding lanes,
        gather leaves back to the default device, drop the mask mirror."""
        b = self.batch_size

        def trim(leaf):
            leaf = leaf[:b] if int(leaf.shape[0]) > b else leaf
            return jax.device_put(leaf)

        self._scn = jax.tree_util.tree_map(trim, self._scn)
        if self._state is not None:
            self._state = jax.tree_util.tree_map(trim, self._state)
        self._mask_dev = None
        self._n_classes_dev = self._n_classes_host = None
        self._resident_mesh = None

    @contextlib.contextmanager
    def _host_geometry(self):
        """Run a lane-geometry mutation (add/remove/compact) in the logical
        host layout, then re-establish residency — so the geometry code
        never has to reason about mesh padding."""
        mesh = self._resident_mesh
        if mesh is None:
            yield
            return
        self._exit_residency()
        try:
            yield
        finally:
            self.make_resident(mesh)

    # ------------------------------------------------------------------ events
    def apply(self, event: StreamEvent) -> Optional[int]:
        """Apply one event; returns the assigned slot for arrivals.

        A one-event :meth:`apply_epoch`: the same validation, the same
        single upload and the same commit program.

        Parameters
        ----------
        event : StreamEvent
            One of ClassArrival, ClassDeparture, SLAEdit, CapacityChange.

        Returns
        -------
        int or None
            The slot granted to a :class:`ClassArrival`, else None.
        """
        return self.apply_epoch([event])[0]

    def apply_epoch(self, events: Sequence[StreamEvent]) -> List[Optional[int]]:
        """Fold MANY events into one atomic, coalesced window update.

        Numerically identical to applying ``events`` one by one with
        :meth:`apply` (same slot assignments, same growth schedule, same
        written values — the per-slot constants come from the same
        :func:`derive` closed forms).  The write path is the same for
        every epoch, one event or many: the host packs the staged raw
        rows, their slots and the capacity updates into a few numpy
        arrays, sends them in ONE upload (:func:`_upload`), and ONE
        compiled program (:func:`_epoch_commit`) derives the classes'
        constants and writes every class field, the resident mask mirror,
        vacated warm-state slots, lane capacities and the rho_hat refresh.
        Nothing is read back from the device.  On a device-resident
        window every dispatch is a full multi-device execution round, so
        the one program matters there twice over.

        The update is atomic: events are validated against a host-side
        simulation of the whole epoch first, so an invalid event (unknown
        lane, departing an empty slot, bad SLA fields) raises before any
        state is mutated.

        Parameters
        ----------
        events : Sequence[StreamEvent]
            Events in application order (the order defines slot assignment
            for arrivals and the merge order of SLA edits).

        Returns
        -------
        list of (int or None)
            One entry per event: the slot granted to a
            :class:`ClassArrival`, None for every other kind.
        """
        events = list(events)
        if not events:
            return []
        # ---- simulate: net per-slot effect + validation, no mutation yet
        sim_mask = self._mask.copy()
        n_max, B = self.n_max, self.batch_size
        staged: Dict[Tuple[int, int], Optional[dict]] = {}  # None = vacated
        vacated = False
        new_R: Dict[int, float] = {}
        granted: List[Optional[int]] = []
        for ev in events:
            if isinstance(ev, ClassArrival):
                self._check_lane(ev.lane)
                missing = set(RAW_CLASS_FIELDS) - set(ev.params)
                if missing:
                    raise ValueError(
                        f"class params missing fields {sorted(missing)}")
                free = np.flatnonzero(~sim_mask[ev.lane])
                if free.size == 0:                  # mirror self.grow
                    grown = grown_n_max(n_max, self.growth_factor)
                    sim_mask = np.concatenate(
                        [sim_mask, np.zeros((B, grown - n_max), bool)], axis=1)
                    n_max = grown
                    free = np.flatnonzero(~sim_mask[ev.lane])
                slot = int(free[0])
                sim_mask[ev.lane, slot] = True
                staged[(ev.lane, slot)] = dict(ev.params)
                granted.append(slot)
                continue
            granted.append(None)
            if isinstance(ev, ClassDeparture):
                self._check_lane(ev.lane)
                if not 0 <= ev.slot < n_max or not sim_mask[ev.lane, ev.slot]:
                    raise IndexError(
                        f"(lane={ev.lane}, slot={ev.slot}) holds no class")
                sim_mask[ev.lane, ev.slot] = False
                staged[(ev.lane, ev.slot)] = None
                vacated = True
            elif isinstance(ev, SLAEdit):
                self._check_lane(ev.lane)
                if not 0 <= ev.slot < n_max or not sim_mask[ev.lane, ev.slot]:
                    raise IndexError(
                        f"(lane={ev.lane}, slot={ev.slot}) holds no class")
                bad = set(ev.updates) - set(RAW_CLASS_FIELDS)
                if bad:
                    raise ValueError(f"unknown raw fields {sorted(bad)}")
                base = (staged[(ev.lane, ev.slot)]
                        if (ev.lane, ev.slot) in staged
                        else self._raw[(ev.lane, ev.slot)])
                staged[(ev.lane, ev.slot)] = {**base, **ev.updates}
            elif isinstance(ev, CapacityChange):
                self._check_lane(ev.lane)
                new_R[ev.lane] = float(ev.R)
            else:
                raise TypeError(f"unknown event {ev!r}")

        # ---- pack on the host (power-of-two buckets bound the variants)
        dt = np.dtype(self._scn.A.dtype)
        classes = capacity = None
        if staged:
            keys = _pad_idx(sorted(staged))
            raw = np.asarray(
                [_NEUTRAL_RAW if staged[k] is None
                 else [staged[k][f] for f in RAW_CLASS_FIELDS]
                 for k in keys], dt)
            idx = np.asarray([(l, s, staged[(l, s)] is not None)
                              for l, s in keys], np.int32)
            classes = (raw, idx, sim_mask[idx[:, 0]])
        if new_R:
            lanes = _pad_idx(sorted(new_R))
            capacity = (np.asarray(lanes, np.int32),
                        np.asarray([new_R[l] for l in lanes], dt))

        # ---- commit: grow once, host bookkeeping, ONE upload + ONE program
        if n_max > self.n_max:
            self.grow(n_max)
        self._mask = sim_mask
        for k, params in staged.items():
            if params is None:
                self._raw.pop(k, None)
            else:
                self._raw[k] = dict(params)
        if staged or new_R:
            state_r = (self._state.r if vacated and self._state is not None
                       else None)
            scn, mask_dev, state_r = _epoch_commit(
                self._scn, self._mask_dev if staged else None, state_r,
                *_upload((classes, capacity)))
            self._scn = scn
            if staged and self._mask_dev is not None:
                self._mask_dev = mask_dev
            if state_r is not None:
                self._state = self._state._replace(r=state_r)
        for lane in {*(k[0] for k in staged), *new_R}:
            self.dirty[lane] = True
            self.baseline_stale[lane] = True
        return granted

    def arrive(self, lane: int, **params) -> int:
        """Admit a new class to ``lane``; returns its slot.

        Parameters
        ----------
        lane : int
            Target lane.
        **params
            Raw per-class scalars, exactly :data:`RAW_CLASS_FIELDS`
            (A, B, E, cM, cR, H_up, H_low, m, rho_up).

        Returns
        -------
        int
            The slot index granted — the lowest free slot; the window grows
            (repads every leaf) only when the lane's row is full.
        """
        return self.apply(ClassArrival(lane=lane, params=params))

    def depart(self, lane: int, slot: int) -> None:
        """Remove the class at (lane, slot); the slot becomes recyclable."""
        self.apply(ClassDeparture(lane=lane, slot=slot))

    def edit(self, lane: int, slot: int, **updates) -> None:
        """Renegotiate the SLA / profile of the class at (lane, slot).

        Parameters
        ----------
        lane, slot : int
            Addressed class (must be admitted).
        **updates
            Subset of :data:`RAW_CLASS_FIELDS` to overwrite; derived
            constants are recomputed from the merged raw parameters.
        """
        self.apply(SLAEdit(lane=lane, slot=slot, updates=updates))

    def set_capacity(self, lane: int, R: float) -> None:
        """Set lane capacity R (node failures / restores, paper Fig. 2)."""
        self.apply(CapacityChange(lane=lane, R=R))

    def grow(self, new_n_max: int) -> None:
        """Repad every (B, n_max) leaf to ``new_n_max`` columns.

        Padding is solver-inert (neutral classes, mask False), so stored
        equilibria of clean lanes remain exact across growth — their padded
        tail contributes 0 to every sum the solver takes.
        """
        old = self.n_max
        if new_n_max <= old:
            raise ValueError(f"new_n_max={new_n_max} must exceed {old}")
        B, pad = self.batch_size, new_n_max - old
        # device leaves may carry mesh-padding lanes (resident layout);
        # grow their actual row count, not the logical B (padding lanes'
        # rho_bar is 1, so their rho_up fill stays the inert 1)
        rows = int(self._scn.A.shape[0])
        dt = self._scn.A.dtype
        neutral = neutral_class_values(0.0)
        kw = {}
        for f in _CLASS_FIELDS:
            leaf = getattr(self._scn, f)
            if f == "rho_up":
                fill = jnp.broadcast_to(self._scn.rho_bar[:, None],
                                        (rows, pad))
            else:
                fill = jnp.full((rows, pad), neutral[f], dt)
            kw[f] = jnp.concatenate([leaf, fill.astype(dt)], axis=1)
        self._scn = self._scn.replace(**kw)
        self._mask = np.concatenate(
            [self._mask, np.zeros((B, pad), bool)], axis=1)
        if self._state is not None:
            st = self._state
            self._state = st._replace(
                r=jnp.concatenate(
                    [st.r, jnp.zeros((int(st.r.shape[0]), pad), dt)],
                    axis=1))
        if self._resident_mesh is not None:
            # column concats may leave fresh leaves unplaced — re-commit
            # everything (device_put is a no-op for already-placed leaves)
            self._place_device_leaves()

    # ------------------------------------------------------- dynamic lanes
    def add_lane(self, scn: Optional[Scenario] = None, *,
                 R: Optional[float] = None,
                 rho_bar: Optional[float] = None) -> int:
        """Append one lane (a new cluster / fleet joining the window).

        The lane row is built by :func:`repro.core.sharding.pad_batch_lanes`
        — the same inert-lane construction the device-sharded solver pads
        ragged fleets with — then overwritten with ``scn`` when given, so a
        batch resident on a lane mesh stays shardable (the mesh path repads
        to the device multiple per solve; see ``sharding.shard_batch``).
        Stored equilibria of existing lanes are untouched; the new lane
        starts dirty/never-solved, so the next ``solve_streaming`` iterates
        exactly it (plus any other dirty lanes).

        Call between solves (flush boundaries): an :class:`EventEpoch` with
        pending events still references pre-growth lane numbering only, so
        ordering is safe, but slot simulation assumes a fixed B per epoch.

        Parameters
        ----------
        scn : Scenario, optional
            Initial classes of the new lane (ragged n is fine; the window
            grows ``n_max`` first if ``scn.n`` exceeds it).  ``None`` admits
            an *empty* lane that later arrivals fill.
        R : float, optional
            Lane capacity, required (with ``rho_bar``) when ``scn`` is None.
        rho_bar : float, optional
            Lane unit chip cost, required (with ``R``) when ``scn`` is None.

        Returns
        -------
        int
            The new lane's index (the previous ``batch_size``).
        """
        if scn is None and (R is None or rho_bar is None):
            raise ValueError("an empty lane needs explicit R= and rho_bar=")
        with self._host_geometry():
            if scn is not None and scn.n > self.n_max:
                self.grow(int(scn.n))
            b = self.batch_size
            dt = self._scn.A.dtype
            self._scn = sharding.pad_batch_lanes(self.batch, b + 1).scenarios
            self._mask = np.concatenate(
                [self._mask, np.zeros((1, self.n_max), bool)], axis=0)
            if scn is not None:
                row = pad_scenario(scn, self.n_max)
                self._scn = self._scn.replace(
                    **{f.name: getattr(self._scn, f.name).at[b].set(
                           jnp.asarray(getattr(row, f.name), dt))
                       for f in dataclasses.fields(Scenario)})
                self._mask[b, :scn.n] = True
                cols = {f: np.asarray(getattr(scn, f))
                        for f in RAW_CLASS_FIELDS}
                for i in range(scn.n):
                    self._raw[(b, i)] = {f: float(cols[f][i])
                                         for f in RAW_CLASS_FIELDS}
            else:
                self._scn = self._scn.replace(
                    R=self._scn.R.at[b].set(float(R)),
                    rho_bar=self._scn.rho_bar.at[b].set(float(rho_bar)),
                    rho_hat=self._scn.rho_hat.at[b].set(float(rho_bar)),
                    rho_up=self._scn.rho_up.at[b].set(
                        jnp.full((self.n_max,), float(rho_bar), dt)))
            if self._state is not None:
                st = self._state
                self._state = st._replace(
                    r=jnp.concatenate([st.r, jnp.zeros((1, self.n_max), dt)],
                                      axis=0),
                    rho=jnp.concatenate([st.rho, jnp.ones((1,), dt)]),
                    lane_iters=jnp.concatenate(
                        [st.lane_iters, jnp.zeros((1,), jnp.int32)]),
                    solved=jnp.concatenate([st.solved,
                                            jnp.zeros((1,), bool)]))
            self.dirty = np.append(self.dirty, True)
            self.baseline_totals = np.append(self.baseline_totals, np.nan)
            self.baseline_stale = np.append(self.baseline_stale, True)
        return b

    def remove_lane(self, lane: int) -> None:
        """Drop ``lane`` (a cluster / fleet leaving) and shrink B by one.

        Lanes above ``lane`` shift down by one; the caller owns any external
        lane-indexed bookkeeping (``cluster.epoch_stream`` does this for its
        fleet list).  Stored equilibria of the surviving lanes move with
        them — clean lanes stay frozen across the shrink.  Like
        :meth:`add_lane`, call at flush boundaries only.
        """
        self._check_lane(lane)
        if self.batch_size == 1:
            raise ValueError("cannot remove the last lane")
        with self._host_geometry():
            self._scn = self._scn.replace(
                **{f.name: jnp.delete(getattr(self._scn, f.name), lane,
                                      axis=0)
                   for f in dataclasses.fields(Scenario)})
            self._mask = np.delete(self._mask, lane, axis=0)
            self.dirty = np.delete(self.dirty, lane)
            self.baseline_totals = np.delete(self.baseline_totals, lane)
            self.baseline_stale = np.delete(self.baseline_stale, lane)
            if self._state is not None:
                st = self._state
                self._state = st._replace(
                    r=jnp.delete(st.r, lane, axis=0),
                    rho=jnp.delete(st.rho, lane),
                    lane_iters=jnp.delete(st.lane_iters, lane),
                    solved=jnp.delete(st.solved, lane))
            self._raw = {(b - (b > lane), s): raw
                         for (b, s), raw in self._raw.items() if b != lane}

    def compact(self, *, n_max: Optional[int] = None) -> np.ndarray:
        """Re-pack every lane's admitted classes into a slot prefix.

        Long-lived windows go sparse: churn leaves holes in the mask and
        growth ratchets ``n_max`` up, so every solve pays O(n_max) for
        classes that are long gone.  Compaction gathers each lane's
        admitted classes down to slots ``0..k-1`` (relative order
        preserved), shrinks ``n_max`` to the widest lane (or the requested
        ``n_max``), and remaps the stored equilibrium and raw-parameter
        book-keeping the same way — so clean lanes stay *frozen* through
        the next solve and every post-compaction solve is numerically
        equivalent (<= 1e-6; bit-equal on backends with order-stable
        reductions) to solving the uncompacted window, just on a smaller
        program.  Dirty flags and memoized centralized baselines are
        untouched (the per-lane scenarios are semantically unchanged).

        Call at flush boundaries only: pending events and previously
        sampled traces address classes by their *old* slots.  The new
        ``n_max`` changes XLA shapes, so the next solve recompiles — that
        one-off cost is why compaction is a policy decision
        (``docs/OPERATIONS.md``), not automatic.

        Parameters
        ----------
        n_max : int, optional
            Target padded width; defaults to the minimal width (the
            largest per-lane class count, floor 1).  Must be >= it.

        Returns
        -------
        np.ndarray
            (B, old_n_max) int map: old slot -> new slot, -1 where the old
            slot held no class.  Callers with slot-addressed bookkeeping
            (e.g. ``cluster.epoch_stream``'s tenant->slot maps) remap
            through it.
        """
        counts = self._mask.sum(axis=1)
        min_width = max(int(counts.max()), 1)
        target = min_width if n_max is None else int(n_max)
        if target < min_width:
            raise ValueError(
                f"n_max={target} below the widest lane ({min_width})")
        B, old = self.batch_size, self.n_max
        slot_map = np.full((B, old), -1, np.int64)
        src = np.zeros((B, target), np.int64)
        for b in range(B):
            occ = np.flatnonzero(self._mask[b])
            slot_map[b, occ] = np.arange(occ.size)
            src[b, :occ.size] = occ
        new_mask = np.arange(target)[None, :] < counts[:, None]
        if target == old and np.array_equal(new_mask, self._mask):
            return slot_map                      # already packed at this width
        with self._host_geometry():
            dt = self._scn.A.dtype
            srcj, nm = jnp.asarray(src), jnp.asarray(new_mask)
            neutral = neutral_class_values(0.0)
            kw = {}
            for f in _CLASS_FIELDS:
                gathered = jnp.take_along_axis(getattr(self._scn, f), srcj,
                                               axis=1)
                if f == "rho_up":
                    fill = jnp.broadcast_to(self._scn.rho_bar[:, None],
                                            (B, target))
                else:
                    fill = jnp.full((B, target), neutral[f], dt)
                kw[f] = jnp.where(nm, gathered, fill).astype(dt)
            self._scn = self._scn.replace(**kw)
            self._mask = new_mask
            self._raw = {(b, int(slot_map[b, s])): raw
                         for (b, s), raw in self._raw.items()}
            if self._state is not None:
                st = self._state
                self._state = st._replace(
                    r=jnp.where(nm, jnp.take_along_axis(st.r, srcj, axis=1),
                                0.0).astype(dt))
        return slot_map

    # ------------------------------------------------------------ solver state
    def warm_start(self) -> game.BatchWarmStart:
        """Incremental-re-solve init for ``solve_distributed_batch``.

        Returns
        -------
        game.BatchWarmStart
            Clean, previously solved lanes are frozen at their stored
            equilibrium (``active`` False — zero iterations); dirty or
            never-solved lanes get the cold Algorithm 4.1 init so they
            reproduce the cold trajectory exactly (see module docstring for
            why bids are never carried over).
        """
        if self._resident_mesh is not None:
            raise RuntimeError(
                "resident windows build their init on-device — use "
                "resident_warm_start (or release_resident first)")
        cold = game.cold_start(self.batch)
        if self._state is None:
            return cold
        st = self._state
        frozen_np = np.asarray(st.solved) & ~self.dirty
        frozen = jnp.asarray(frozen_np)
        keep = frozen[:, None]
        return game.BatchWarmStart(
            r=jnp.where(keep, st.r, cold.r),
            bids=cold.bids,
            rho=jnp.where(frozen, st.rho, cold.rho),
            lane_iters=jnp.where(frozen, st.lane_iters,
                                 jnp.zeros_like(st.lane_iters)),
            active=~frozen)

    def commit(self, r, rho, lane_iters) -> None:
        """Store a fresh equilibrium and mark every lane clean.

        Parameters
        ----------
        r : jnp.ndarray
            (B, n_max) equilibrium allocation of the just-finished solve
            (a resident solve commits the PADDED lane count — the mesh
            padding rows stay part of the stored state).
        rho : jnp.ndarray
            (B,) final RM prices (``Solution.aux``).
        lane_iters : jnp.ndarray
            (B,) per-lane iteration counts (``Solution.iters``).
        """
        dt = self._scn.A.dtype
        self._state = WindowState(
            r=jnp.asarray(r, dt),
            rho=jnp.asarray(rho, dt),
            lane_iters=jnp.asarray(lane_iters, jnp.int32),
            solved=jnp.ones((int(np.shape(r)[0]),), bool))
        self.dirty[:] = False

    # -------------------------------------------------------------- internals
    def _check_lane(self, lane: int) -> None:
        if not 0 <= lane < self.batch_size:
            raise IndexError(f"lane {lane} out of range [0, {self.batch_size})")


def grown_n_max(n_max: int, growth_factor: float) -> int:
    """Deterministic growth schedule shared by the window and trace tools.

    Parameters
    ----------
    n_max : int
        Current padded width.
    growth_factor : float
        Multiplicative headroom (> 1).

    Returns
    -------
    int
        ``max(ceil(growth_factor * n_max), n_max + 1)``.
    """
    return max(int(math.ceil(n_max * growth_factor)), n_max + 1)


# --------------------------------------------------------------------------
# Event coalescing: fold many events into one re-solve epoch
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class FlushPolicy:
    """When should a buffered event epoch stop accumulating and re-solve?

    The re-solve cadence is the operator's real control knob (see
    ``docs/OPERATIONS.md``): coalescing K events per solve amortizes the
    per-solve dispatch cost ~K-fold at the price of K events of equilibrium
    staleness.  Count/fraction triggers compose with OR; a policy with both
    None never auto-flushes (purely manual ``flush`` calls).  On top of
    the bulk triggers, the *deadline-aware* triggers (see
    :meth:`deadline`) force an immediate flush for SLA-critical events —
    an :class:`~repro.core.types.SLAEdit` tightening a deadline, or an
    arrival whose deadline is already nearly exhausted — so the game
    re-equilibrates before a critical class waits out a whole epoch, while
    bulk events keep coalescing.

    Attributes
    ----------
    max_events : int, optional
        Flush once this many events are buffered (the latency bound: no
        admitted class waits more than ``max_events`` events for capacity).
    max_dirty_fraction : float, optional
        Flush once the prospective dirty-lane fraction (window-dirty plus
        buffered lanes, over B) reaches this value.  Past ~0.5 the
        frozen-lane saving of the warm start is mostly gone, so waiting
        longer buys staleness without saving work.
    deadline_slack_s : float, optional
        SLA-criticality threshold on ``E = C - D`` (< 0 when the deadline
        is attainable): an arrival or deadline edit landing at
        ``E >= -deadline_slack_s`` — within ``deadline_slack_s`` seconds
        of an unattainable deadline — flushes immediately.  ``None``
        (default) disables the trigger.
    flush_on_sla_tightening : bool
        Flush immediately on any :class:`~repro.core.types.SLAEdit` that
        *tightens* a class's deadline (raises its ``E`` toward 0), however
        much slack remains — the renegotiation the paper's runtime loop
        reacts to fastest.
    """
    max_events: Optional[int] = 8
    max_dirty_fraction: Optional[float] = None
    deadline_slack_s: Optional[float] = None
    flush_on_sla_tightening: bool = False

    @classmethod
    def deadline(cls, slack_s: float, *, max_events: Optional[int] = 64,
                 max_dirty_fraction: Optional[float] = None,
                 tightening: bool = True) -> "FlushPolicy":
        """Deadline-aware policy: SLA-critical events flush immediately.

        ``Policies(flush=FlushPolicy.deadline(30.0))`` gives the paper's
        runtime loop a two-speed cadence: bulk churn (arrivals with ample
        slack, departures, capacity steps) coalesces up to ``max_events``
        per re-solve, while a deadline-critical event — a class arriving
        within ``slack_s`` seconds of infeasibility, or an SLA edit
        tightening a deadline — re-equilibrates the game at once.

        Parameters
        ----------
        slack_s : float
            Criticality threshold [s] on ``E = C - D``: events with
            ``E >= -slack_s`` are critical.
        max_events : int, optional
            Bulk coalescing bound (default 64 — deliberately loose; the
            deadline triggers carry the latency guarantee).
        max_dirty_fraction : float, optional
            Optional bulk dirty-fraction trigger, as on the default policy.
        tightening : bool, optional
            Also flush on every deadline-tightening SLA edit (default
            True).

        Returns
        -------
        FlushPolicy
            The configured policy.
        """
        return cls(max_events=max_events,
                   max_dirty_fraction=max_dirty_fraction,
                   deadline_slack_s=float(slack_s),
                   flush_on_sla_tightening=tightening)

    def is_critical(self, event: StreamEvent,
                    window: "AdmissionWindow") -> bool:
        """Does ``event`` demand an immediate flush (deadline triggers)?

        Parameters
        ----------
        event : StreamEvent
            The event being buffered.
        window : AdmissionWindow
            The live window — consulted for the edited class's current
            ``E`` so *tightening* is judged against the last applied state
            (an edit to a class that itself arrived earlier in the same
            epoch is judged by the slack threshold only).

        Returns
        -------
        bool
            True when a deadline trigger fires; always False for policies
            without deadline triggers configured.
        """
        slack = self.deadline_slack_s
        if isinstance(event, ClassArrival):
            return (slack is not None
                    and float(event.params.get("E", -np.inf)) >= -slack)
        if isinstance(event, SLAEdit) and "E" in event.updates:
            new_E = float(event.updates["E"])
            if slack is not None and new_E >= -slack:
                return True
            if self.flush_on_sla_tightening:
                old = window._raw.get((event.lane, event.slot))
                return old is not None and new_E > float(old["E"])
        return False

    def should_flush(self, *, n_events: int, n_dirty: int,
                     batch_size: int) -> bool:
        """Evaluate the triggers against an epoch's current accumulation.

        Parameters
        ----------
        n_events : int
            Events buffered so far.
        n_dirty : int
            Prospective dirty lanes of the flush (window dirty | buffered).
        batch_size : int
            Window lane count B.

        Returns
        -------
        bool
            True when any configured trigger fires.
        """
        if self.max_events is not None and n_events >= self.max_events:
            return True
        if (self.max_dirty_fraction is not None and batch_size > 0
                and n_dirty / batch_size >= self.max_dirty_fraction):
            return True
        return False


class EventEpoch:
    """Accumulate events against a window; one coalesced solve per flush.

    The coalescing layer between per-event streaming (PR 2) and the
    operator's cadence policy: events buffer on the host (zero device
    work), and :meth:`flush` folds them into the window with ONE upload
    and ONE commit program (:meth:`AdmissionWindow.apply_epoch`) followed
    by ONE warm-started ``solve_streaming`` over the union of dirtied
    lanes.
    Replaying a trace through epochs lands on exactly the per-event
    equilibria at every flush boundary: a lane dirtied anywhere in the
    epoch restarts from the cold Algorithm 4.1 init on its *final*
    scenario, which is precisely what the last per-event solve would have
    computed (``tests/test_coalescing.py``).

    Parameters
    ----------
    window : AdmissionWindow
        The live window; mutated only at flush.
    policy : FlushPolicy, optional
        Auto-flush triggers consulted by :meth:`add` (default: flush every
        8 events).

    Attributes
    ----------
    flushes : int
        Completed flushes.
    events_folded : int
        Total events applied across all flushes.
    last_slots : list
        Per-event slot grants of the most recent flush (see
        :meth:`AdmissionWindow.apply_epoch`).
    """

    def __init__(self, window: AdmissionWindow,
                 policy: Optional[FlushPolicy] = None):
        self.window = window
        self.policy = policy or FlushPolicy()
        self._events: List[StreamEvent] = []
        self.flushes = 0
        self.events_folded = 0
        self.last_slots: List[Optional[int]] = []

    def __len__(self) -> int:
        return len(self._events)

    @property
    def pending(self) -> Tuple[StreamEvent, ...]:
        """Buffered, not-yet-applied events (application order)."""
        return tuple(self._events)

    @property
    def dirty_lanes(self) -> Set[int]:
        """Lanes the next flush will re-solve: window-dirty | buffered."""
        return (set(np.flatnonzero(self.window.dirty))
                | {ev.lane for ev in self._events})

    def add(self, event: StreamEvent) -> bool:
        """Buffer one event; report whether the policy wants a flush.

        Parameters
        ----------
        event : StreamEvent
            Any of the four event kinds; validated at flush (atomically,
            see :meth:`AdmissionWindow.apply_epoch`).

        Returns
        -------
        bool
            True when the flush policy's triggers fire — including an
            SLA-critical event under a deadline-aware policy — and the
            caller should :meth:`flush` (``WindowSession.stream`` does).
        """
        self._events.append(event)
        return (self.policy.is_critical(event, self.window)
                or self.policy.should_flush(
                    n_events=len(self._events),
                    n_dirty=len(self.dirty_lanes),
                    batch_size=self.window.batch_size))

    def flush(self, **solve_kwargs):
        """Apply the buffered events and re-solve the window once.

        Parameters
        ----------
        **solve_kwargs
            Legacy solver kwargs (``mesh=``, ``integer=``, solver knobs,
            ...) mapped onto a config/policy pair by
            ``engine._legacy_solve_window``.

        Returns
        -------
        repro.core.engine.WindowSolveReport
            The coalesced re-solve (an empty flush with a clean window is
            legal and nearly free: every lane freezes).
        """
        from repro.core.engine import _legacy_solve_window
        self.last_slots = self.window.apply_epoch(self._events)
        self.events_folded += len(self._events)
        self._events = []
        res = _legacy_solve_window(self.window, **solve_kwargs)
        self.flushes += 1
        return res


# --------------------------------------------------------------------------
# Event-trace generation (tests + benchmarks/streaming_perf.py)
# --------------------------------------------------------------------------


def sample_event_trace(seed: int, window: AdmissionWindow, n_events: int, *,
                       p_arrive: float = 0.45, p_depart: float = 0.30,
                       p_edit: float = 0.15, p_capacity: float = 0.10,
                       params_fn=None) -> List[StreamEvent]:
    """Random, replayable event trace applicable to ``window`` (unmutated).

    The generator simulates the window's slot-assignment and growth rules on
    a host-side copy of the occupancy mask, so departure / edit events always
    address slots that will actually be occupied when the trace is applied in
    order — the same trace can therefore be replayed against an identically
    initialised second window (the cold baseline of the benchmark).

    Parameters
    ----------
    seed : int
        Seeds both the structural RNG and the per-arrival parameter draws.
    window : AdmissionWindow
        Snapshot defining initial occupancy, ``n_max`` and growth factor.
    n_events : int
        Trace length.
    p_arrive, p_depart, p_edit, p_capacity : float, optional
        Event-kind mixture (renormalised).  Kinds that are momentarily
        impossible (departing from an all-empty window) fall back to arrival.
    params_fn : callable, optional
        ``params_fn(jax_key) -> dict`` drawing one class's raw parameters;
        defaults to :func:`repro.core.profiles.sample_class_params`
        (the paper's Table 5 design of experiments).

    Returns
    -------
    list of StreamEvent
        Events in application order.
    """
    params_fn = params_fn or sample_class_params
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    probs = np.asarray([p_arrive, p_depart, p_edit, p_capacity], float)
    probs = probs / probs.sum()

    mask = window._mask.copy()
    n_max = window.n_max
    R = np.asarray(window._scn.R, float).copy()
    B = mask.shape[0]

    events: List[StreamEvent] = []
    for _ in range(n_events):
        kind = rng.choice(4, p=probs)
        occupied = np.argwhere(mask)
        if kind in (1, 2) and occupied.size == 0:
            kind = 0
        if kind == 0:                                   # arrival
            lane = int(rng.integers(B))
            key, sub = jax.random.split(key)
            events.append(ClassArrival(lane=lane, params=params_fn(sub)))
            free = np.flatnonzero(~mask[lane])
            if free.size == 0:                          # mirror window.grow
                new = grown_n_max(n_max, window.growth_factor)
                mask = np.concatenate(
                    [mask, np.zeros((B, new - n_max), bool)], axis=1)
                n_max = new
                free = np.flatnonzero(~mask[lane])
            mask[lane, int(free[0])] = True
        elif kind == 1:                                 # departure
            lane, slot = occupied[rng.integers(len(occupied))]
            events.append(ClassDeparture(lane=int(lane), slot=int(slot)))
            mask[lane, slot] = False
        elif kind == 2:                                 # SLA edit
            lane, slot = occupied[rng.integers(len(occupied))]
            key, sub = jax.random.split(key)
            fresh = params_fn(sub)
            events.append(SLAEdit(
                lane=int(lane), slot=int(slot),
                updates={k: fresh[k]
                         for k in ("E", "m", "rho_up", "H_up", "H_low")}))
        else:                                           # capacity change
            lane = int(rng.integers(B))
            R[lane] *= float(rng.uniform(0.9, 1.1))
            events.append(CapacityChange(lane=lane, R=float(R[lane])))
    return events


def replay(window: AdmissionWindow, events: Sequence[StreamEvent]) -> None:
    """Apply ``events`` to ``window`` in order (no solving).

    Parameters
    ----------
    window : AdmissionWindow
        Mutated in place.
    events : Sequence[StreamEvent]
        A trace, e.g. from :func:`sample_event_trace`.
    """
    for ev in events:
        window.apply(ev)
