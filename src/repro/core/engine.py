"""Unified session API over the GNEP solver stack: one engine, one config.

PRs 1-4 grew the paper's runtime capacity-allocation dynamic into seven
divergent entry points (``solve``, ``solve_batch``, ``solve_streaming``,
``solve_coalesced``, ``solve_centralized[_batch]``, ``solve_sharded_batch``),
each re-threading the same ``eps_bar`` / ``lam`` / ``mesh`` / ``sweep_fn``
kwargs.  This module replaces that zoo with a single configured-session
abstraction (the shape design tools like D-SPACE4Cloud converge on):

* :class:`SolverConfig` — every Algorithm 4.1 knob plus kernel and device
  placement in one frozen, hashable object (``eps_bar``, ``lam``,
  ``max_iters``, ``dtype``, ``sweep_fn``, ``mesh``) with a stable
  :meth:`~SolverConfig.fingerprint` the benchmark regression gate records;
* :class:`Policies` — the *operational* choices as explicit policy objects:
  flush cadence (:class:`~repro.core.streaming.FlushPolicy`, including the
  deadline-aware constructor), compaction occupancy
  (:class:`CompactionPolicy`), Algorithm 4.2 rounding
  (:class:`RoundingPolicy`) and the exact centralized (P3) cross-check
  baseline (:class:`CrossCheckPolicy`);
* :class:`CapacityEngine` — a small verb set: :meth:`~CapacityEngine.solve`
  for one-shot instances/batches and :meth:`~CapacityEngine.open_window`
  for the paper's runtime loop;
* :class:`WindowSession` — the live loop: ``apply`` events, ``flush``
  coalesced re-solves, ``stream`` whole traces; warm-start state, the
  coalescing FlushPolicy loop and mesh placement all live inside;
* the :class:`SolveReport` hierarchy — one result shape (equilibrium,
  per-lane iterations/convergence, rounding, centralized gap, timing)
  subsuming the legacy ``AllocationResult`` / ``BatchAllocationResult`` /
  ``StreamingResult``.

The legacy ``repro.core.allocator.solve_*`` facades are thin deprecated
shims over this module, proven bit-equal in ``tests/test_engine.py``; the
old-call -> engine-call migration table is ``docs/API.md``.  ``game.py`` /
``streaming.py`` / ``sharding.py`` / ``centralized.py`` stay pure mechanism:
adding a new backend kernel or event kind is a config/policy field here, not
another ``solve_*`` variant.
"""
from __future__ import annotations

import re
import time
from dataclasses import dataclass, replace
from typing import (Any, Callable, Iterable, Iterator, List, Optional,
                    Sequence, Set, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import game, sharding
from repro.core.centralized import solve_centralized
from repro.core.rounding import (IntegerSolution, round_solution,
                                 round_solution_batch)
from repro.core.streaming import AdmissionWindow, FlushPolicy
from repro.core.types import (ClassArrival, Scenario, ScenarioBatch, SLAEdit,
                              Solution, StreamEvent, stack_scenarios)
from repro.spans import span


class InfeasibleError(RuntimeError):
    """Deadlines/SLAs cannot be met with the available capacity."""


class QuotaExceededError(RuntimeError):
    """A session operation would exceed its :class:`TenantQuota`.

    Raised by :meth:`WindowSession.offer` (event budget) and
    :meth:`WindowSession.add_lane` (lane budget).  External schedulers like
    ``repro.serving.allocd`` check the quota *before* handing an event to
    the session (the rejection then carries the paper's rejection cost
    instead of an exception), so in a correctly plumbed daemon this error
    is the backstop, not the control path.
    """


@dataclass(frozen=True)
class TenantQuota:
    """Per-tenant admission budget enforced by a :class:`WindowSession`.

    The multi-tenant generalization of the daemon-wide queue bound: each
    tenant gets its own event and lane budget so one tenant's burst can
    never exhaust the shared daemon's headroom (the daemon-wide bound
    remains as a backstop).  ``None`` fields are unlimited.

    Attributes
    ----------
    max_queued : int, optional
        Upper bound on this tenant's not-yet-flushed events — in daemon
        terms the sum of its queued and already-buffered (in-epoch) events;
        for a bare session, the buffered-event count :meth:`WindowSession.offer`
        enforces.  Submissions beyond it are rejected and charged the
        paper's rejection penalty (``m * H_up`` for a class arrival).
    max_lanes : int, optional
        Upper bound on the tenant's open window lanes:
        :meth:`WindowSession.add_lane` refuses to grow past it, and a
        daemon refuses to register a tenant whose initial window is
        already wider.
    """
    max_queued: Optional[int] = None
    max_lanes: Optional[int] = None

    def admits_event(self, n_queued: int) -> bool:
        """Whether one more event fits under ``max_queued``.

        Parameters
        ----------
        n_queued : int
            Events currently queued/buffered against this quota.

        Returns
        -------
        bool
            True when unlimited or ``n_queued < max_queued``.
        """
        return self.max_queued is None or n_queued < self.max_queued

    def admits_lane(self, n_lanes: int) -> bool:
        """Whether one more lane fits under ``max_lanes``.

        Parameters
        ----------
        n_lanes : int
            Lanes currently open against this quota.

        Returns
        -------
        bool
            True when unlimited or ``n_lanes < max_lanes``.
        """
        return self.max_lanes is None or n_lanes < self.max_lanes


# --------------------------------------------------------------------------
# Configuration: every solver knob in one frozen object
# --------------------------------------------------------------------------


_F32_CHECKED_RE = re.compile(r"f32_checked(?:\[:([1-9]\d*)\])?$")


def _parse_dtype_policy(policy: str):
    """Parse a ``SolverConfig.dtype_policy`` string.

    Parameters
    ----------
    policy : str
        ``"f64"``, ``"f32_checked"`` or ``"f32_checked[:k]"``.

    Returns
    -------
    tuple or None
        ``("f64", None)`` or ``("f32_checked", k)`` (k defaults to 4);
        None when the string is not a valid policy.
    """
    if policy == "f64":
        return ("f64", None)
    m = _F32_CHECKED_RE.fullmatch(policy)
    if m:
        return ("f32_checked", int(m.group(1)) if m.group(1) else 4)
    return None


@dataclass(frozen=True)
class SolverConfig:
    """Every Algorithm 4.1 knob, kernel choice and placement in one object.

    Frozen and hashable (safe as a static jit argument and as a dict key),
    with float/int leaves only — pytree-friendly by construction.  One
    config replaces the six kwargs the legacy facades threaded separately;
    the engine passes it to every mechanism call so no path can silently
    drop a knob (the kwargs-drift class of bug the redesign retires).

    Attributes
    ----------
    eps_bar : float
        Algorithm 4.1 stopping tolerance on the relative allocation change
        ``sum_i |r_i' - r_i| / r_i`` (paper uses 0.03).
    lam : float
        Bid-escalation (pseudo-gradient) step of ``game.cm_bid_update``: a
        rejecting CM raises its bid by ``lam * rho_up`` per iteration.
    max_iters : int
        Best-reply iteration cap (a static jit argument: changing it
        recompiles).
    dtype : jnp.dtype or str, optional
        Float dtype scenario leaves are coerced to by :func:`_coerce`.
        ``None`` (default) keeps each input's native dtype.  Mutually
        exclusive with ``dtype_policy`` (which subsumes it).
    dtype_policy : str, optional
        Checked precision policy, the supported alternative to raw
        ``dtype``: ``"f64"`` coerces every solve to float64 (the bit
        authority); ``"f32_checked"`` (optionally ``"f32_checked[:k]"``,
        default k=4) runs the fast float32 path and then re-solves ``k``
        evenly-spaced sample lanes of every batched/streaming solve in
        float64 on the unfused reference path, raising ``RuntimeError``
        (naming the lanes) if any sampled lane's allocation deviates
        beyond the documented bound ``2 * eps_bar`` relative — both
        precisions are ``eps_bar``-converged equilibria of the same
        game, so they can legitimately sit anywhere inside one stopping
        tolerance of each other, and the check flags anything worse.
        Reports carry the measurement in ``dtype_check``.  ``None``
        (default) applies no policy.  See docs/OPERATIONS.md for how to
        choose (and the CPU-runner caveats).
    sweep_fn : callable, optional
        Batched RM price-sweep override, e.g. the Pallas kernel from
        ``repro.kernels.gnep_sweep.ops.make_batched_sweep_fn`` — applied on
        every batched/streaming solve.  Pass a memoized function object
        (it keys the compiled-program caches by identity).
    iter_fn : object, optional
        Fused-iteration override, e.g.
        ``repro.kernels.gnep_iter.ops.make_fused_iter_fn()``: the whole
        Alg. 4.1 inner iteration (sweep + best responses + bid update +
        eps) runs as one fused step per while-loop body, with the
        iteration-invariant prep hoisted out of the loop.  Takes
        precedence over ``sweep_fn`` on every batched/streaming solve.
        Pass a memoized object (identity keys the compiled-program
        caches); its ``__name__`` is recorded in the fingerprint.
    mesh : jax.sharding.Mesh, optional
        1-D lane mesh (``repro.core.sharding.lane_mesh``): batched and
        streaming solves shard their lanes across the mesh's devices,
        inert-lane padding handling ragged lane counts.  ``None`` keeps
        everything on one device.
    residency : str
        Where a :class:`WindowSession`'s state lives between flushes.
        ``"round-trip"`` (default) re-places the window on the mesh every
        solve — simple, but the per-flush host<->device resharding is why
        sharded streaming historically scaled *worse* than unsharded.
        ``"resident"`` (requires ``mesh``) keeps the window's scenario
        leaves, occupancy-mask mirror and warm-start state lane-sharded on
        the mesh across flushes (``AdmissionWindow.make_resident``), with
        the warm-start buffers donated between consecutive solves —
        bit-equal results (``tests/test_resident.py``), no per-flush
        resharding.  One-shot ``solve`` calls are unaffected.
    """
    eps_bar: float = 0.03
    lam: float = 0.05
    max_iters: int = 200
    dtype: Optional[Any] = None
    sweep_fn: Optional[Callable] = None
    mesh: Optional[Any] = None
    residency: str = "round-trip"
    iter_fn: Optional[Any] = None
    dtype_policy: Optional[str] = None

    def __post_init__(self):
        if self.dtype_policy is None:
            return
        if self.dtype is not None:
            raise ValueError(
                "dtype= and dtype_policy= are mutually exclusive — "
                "dtype_policy subsumes the cast (use dtype_policy alone)")
        if _parse_dtype_policy(self.dtype_policy) is None:
            raise ValueError(
                f"unknown dtype_policy {self.dtype_policy!r} — expected "
                "'f64', 'f32_checked' or 'f32_checked[:k]' with k >= 1")

    def effective_dtype(self):
        """The dtype scenario leaves are coerced to under this config.

        Returns
        -------
        jnp.dtype or None
            ``dtype_policy``'s cast when a policy is set (f64 / f32),
            otherwise the raw ``dtype`` knob (``None`` = keep native).
        """
        if self.dtype_policy is None:
            return self.dtype
        mode, _ = _parse_dtype_policy(self.dtype_policy)
        return jnp.float64 if mode == "f64" else jnp.float32

    def check_sample(self) -> int:
        """Sample-lane count of the ``f32_checked`` cross-check (0 if the
        policy does not check)."""
        if self.dtype_policy is None:
            return 0
        mode, k = _parse_dtype_policy(self.dtype_policy)
        return k if mode == "f32_checked" else 0

    def fingerprint(self) -> str:
        """Stable identity string for benchmark / baseline provenance.

        ``benchmarks/*_perf.py --json`` records it and
        ``scripts/check_bench.py`` treats it as configuration: numbers
        measured under different solver configs (or on the pre-redesign
        facades, which recorded none) are never compared.

        Returns
        -------
        str
            ``eps_bar=..|lam=..|max_iters=..|dtype=..|sweep=..|mesh=..``;
            the sweep kernel contributes its ``__name__``, the mesh its
            shape and axis names.  Non-default ``residency`` / ``iter_fn``
            / ``dtype_policy`` append ``|residency=..`` / ``|iter=..`` /
            ``|dtype_policy=..`` in that order (defaults append nothing,
            so fingerprints recorded before each knob existed stay
            comparable).
        """
        dtype = ("native" if self.dtype is None
                 else jnp.dtype(self.dtype).name)
        sweep = ("reference" if self.sweep_fn is None
                 else getattr(self.sweep_fn, "__name__",
                              type(self.sweep_fn).__name__))
        mesh = ("none" if self.mesh is None
                else "x".join(map(str, self.mesh.devices.shape))
                + ":" + ",".join(self.mesh.axis_names))
        tail = ("" if self.residency == "round-trip"
                else f"|residency={self.residency}")
        if self.iter_fn is not None:
            tail += "|iter=" + getattr(self.iter_fn, "__name__",
                                       type(self.iter_fn).__name__)
        if self.dtype_policy is not None:
            tail += f"|dtype_policy={self.dtype_policy}"
        return (f"eps_bar={self.eps_bar}|lam={self.lam}"
                f"|max_iters={self.max_iters}|dtype={dtype}"
                f"|sweep={sweep}|mesh={mesh}{tail}")


# --------------------------------------------------------------------------
# Policies: the operational choices, as explicit objects
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class RoundingPolicy:
    """Whether (and that) Algorithm 4.2 integerization runs after the solve.

    Attributes
    ----------
    enabled : bool
        Apply the (vectorized) Algorithm 4.2 rounding pass; reports carry
        ``integer=None`` when disabled (what-if sweeps and benchmarks that
        time the fractional solve alone turn it off).
    """
    enabled: bool = True


@dataclass(frozen=True)
class CrossCheckPolicy:
    """Compare every lane against its exact centralized (P3) optimum.

    When enabled, window solves attach the per-lane relative gap of the
    GNEP total over the exact optimum (``SolveReport.centralized_gap``).
    Baseline totals are memoized per lane in the window and recomputed only
    for lanes whose scenario changed, mirroring the incremental solve.

    Attributes
    ----------
    enabled : bool
        Run the baseline (default off — it costs one water-filling solve
        per stale lane).
    atol : float
        Absolute slack allowed in the sanity direction: a feasible lane's
        GNEP total undercutting the exact optimum by more than this raises
        ``RuntimeError`` (impossible for a correct solver).
    """
    enabled: bool = False
    atol: float = 1e-6


@dataclass(frozen=True)
class CompactionPolicy:
    """When a :class:`WindowSession` re-packs its sparse window.

    Churn leaves holes in the occupancy mask and growth ratchets ``n_max``
    up; solver work scales with ``B x n_max``, so long-lived windows slowly
    pay for ghosts.  At every flush boundary the session compares
    ``window.occupancy`` against ``occupancy`` and compacts
    (``AdmissionWindow.compact``) when it drops below — the report carries
    the old->new ``slot_map`` so slot-addressed bookkeeping can follow.

    Attributes
    ----------
    occupancy : float, optional
        Occupied-slot fraction below which the session compacts at the
        next flush boundary.  ``None`` (default) never auto-compacts
        (compaction changes XLA shapes — one recompile — so it stays an
        explicit operator decision; see ``docs/OPERATIONS.md``).
    headroom : float
        Width multiplier for the compacted window: the target ``n_max`` is
        ``ceil(headroom * widest lane)`` (floor: the widest lane), so a
        value > 1 leaves slack before the next arrival forces a re-grow.
    """
    occupancy: Optional[float] = None
    headroom: float = 1.0


@dataclass(frozen=True)
class Policies:
    """The engine's operational policy bundle (all fields are policies).

    Attributes
    ----------
    flush : repro.core.streaming.FlushPolicy
        When buffered events force a coalesced re-solve — including the
        deadline-aware triggers of ``FlushPolicy.deadline`` (SLA-critical
        events flush immediately, bulk events keep coalescing).
    compaction : CompactionPolicy
        When a sparse long-lived window is re-packed.
    rounding : RoundingPolicy
        Whether Algorithm 4.2 integerization runs.
    cross_check : CrossCheckPolicy
        Whether window solves attach the exact (P3) baseline gap.
    """
    flush: FlushPolicy = FlushPolicy()
    compaction: CompactionPolicy = CompactionPolicy()
    rounding: RoundingPolicy = RoundingPolicy()
    cross_check: CrossCheckPolicy = CrossCheckPolicy()


# --------------------------------------------------------------------------
# The unified report hierarchy
# --------------------------------------------------------------------------


@dataclass
class SolveReport:
    """One solved instance: the unified result shape of the engine.

    Subsumes the legacy ``AllocationResult`` (its alias since the engine
    redesign): same core fields, plus the config fingerprint and host-side
    timing every engine call attaches.

    Attributes
    ----------
    method : str
        ``"centralized"``, ``"distributed"``, ``"distributed-python"``,
        ``"distributed-batch"`` or ``"streaming"``.
    fractional : Solution
        The fractional equilibrium / optimum.
    integer : IntegerSolution or None
        Algorithm 4.2 integerization (None when rounding is disabled).
    iters : int or jnp.ndarray
        Best-reply iterations (per lane for batched reports).
    config : SolverConfig or None
        The solver config that produced this report.
    elapsed_s : float
        Host-side wall-clock of the engine call (dispatch + rounding; on
        async backends the device work may still be in flight).
    """
    method: str
    fractional: Solution
    integer: Optional[IntegerSolution]
    iters: Any
    config: Optional[SolverConfig] = None
    elapsed_s: float = 0.0

    @property
    def r(self):
        """Allocation of the preferred (integer when present) solution."""
        return self.integer.r if self.integer is not None else self.fractional.r

    @property
    def total(self):
        """Objective total of the preferred solution."""
        return (self.integer.total if self.integer is not None
                else self.fractional.total)

    @property
    def converged(self):
        """Whether Algorithm 4.1 stopped on tolerance, not the iteration cap
        (per lane for batched reports; trivially True for closed forms)."""
        limit = self.config.max_iters if self.config is not None else np.inf
        return self.iters < limit


@dataclass
class BatchSolveReport(SolveReport):
    """One batched solve: every leaf carries a leading B dim.

    Subsumes the legacy ``BatchAllocationResult`` (its alias).  Per-class
    arrays are (B, n_max) with padded classes identically zero;
    :meth:`instance` trims one lane back to a single-instance
    :class:`SolveReport`.

    Attributes (beyond :class:`SolveReport`)
    ----------------------------------------
    mask : jnp.ndarray
        (B, n_max) class-validity mask of the solved batch.
    n_classes : jnp.ndarray
        (B,) valid-class counts.
    feasible : jnp.ndarray
        (B,) per-lane feasibility flags (``sum(r_low) <= R`` and all
        ``E_i < 0``).
    dtype_check : dict or None
        The ``dtype_policy="f32_checked"`` measurement: sampled ``lanes``,
        worst per-lane relative allocation deviation ``max_rel`` vs the
        f64 reference re-solve, and the ``bound`` it was held to.  None
        when no checking policy is active.
    """
    mask: Optional[jnp.ndarray] = None
    n_classes: Optional[jnp.ndarray] = None
    feasible: Optional[jnp.ndarray] = None
    dtype_check: Optional[dict] = None

    @property
    def batch_size(self) -> int:
        """Number of lanes B in this report."""
        return self.mask.shape[0]

    def instance(self, b: int) -> SolveReport:
        """Trim lane ``b`` to a single-instance view.

        Mask-aware: works for streaming windows whose free slots leave
        holes in the mask (gathers valid slots, never slices a prefix).

        Parameters
        ----------
        b : int
            Lane index.

        Returns
        -------
        SolveReport
            The lane's solution with per-class leaves trimmed to its valid
            classes.
        """
        sel = np.asarray(self.mask[b])

        def pick(leaf):
            leaf = leaf[b]
            return leaf[sel] if getattr(leaf, "ndim", 0) else leaf

        frac = jax.tree_util.tree_map(pick, self.fractional)
        integ = (jax.tree_util.tree_map(pick, self.integer)
                 if self.integer is not None else None)
        return SolveReport(method=self.method, fractional=frac, integer=integ,
                           iters=int(self.iters[b]), config=self.config,
                           elapsed_s=self.elapsed_s)


@dataclass
class WindowSolveReport(BatchSolveReport):
    """One streaming re-solve: a batch report plus incremental bookkeeping.

    Subsumes the legacy ``StreamingResult`` (its alias).

    Attributes (beyond :class:`BatchSolveReport`)
    ---------------------------------------------
    resolved : np.ndarray
        (B,) bool — lanes that actually iterated this solve (dirty or
        never-solved); the complement was frozen at its stored equilibrium.
    centralized_gap : jnp.ndarray or None
        (B,) relative gap of the fractional GNEP total over the exact
        centralized (P3) optimum, when the cross-check policy is enabled.
    slot_map : np.ndarray or None
        (B, old_n_max) old-slot -> new-slot map when this flush compacted
        the window under a :class:`CompactionPolicy` (None otherwise);
        callers with slot-addressed bookkeeping remap through it.
    """
    resolved: Optional[np.ndarray] = None
    centralized_gap: Optional[jnp.ndarray] = None
    slot_map: Optional[np.ndarray] = None


# --------------------------------------------------------------------------
# Input coercion: one helper, every entry point
# --------------------------------------------------------------------------


def _coerce(problem, *, dtype=None, n_max: Optional[int] = None
            ) -> ScenarioBatch:
    """Normalize any accepted problem form into a :class:`ScenarioBatch`.

    The single input-coercion point of the engine (every verb routes
    through it), retiring the legacy drift where ``solve_batch`` accepted a
    ``Sequence[Scenario]`` but the streaming facades did not.

    Parameters
    ----------
    problem : ScenarioBatch, Scenario, Sequence[Scenario] or AdmissionWindow
        A prepared batch (returned as-is, modulo dtype), a single instance
        (stacked as one lane), a plain — possibly ragged — scenario list
        (stacked/padded here), or a live window (its current batch).
    dtype : jnp.dtype or str, optional
        Cast every float leaf to this dtype (``SolverConfig.dtype``);
        ``None`` keeps the input's native dtypes.
    n_max : int, optional
        Padded width passed to ``stack_scenarios`` when stacking loose
        scenarios (ignored for already-stacked inputs).

    Returns
    -------
    ScenarioBatch
        The canonical stacked + masked form every solver consumes.

    Raises
    ------
    TypeError
        For anything else (with the accepted forms named).
    """
    if isinstance(problem, AdmissionWindow):
        batch = problem.batch
    elif isinstance(problem, ScenarioBatch):
        batch = problem
    elif isinstance(problem, Scenario):
        batch = stack_scenarios([problem], n_max=n_max)
    elif isinstance(problem, Sequence) and not isinstance(problem, (str, bytes)):
        items = list(problem)
        if not all(isinstance(s, Scenario) for s in items):
            raise TypeError(
                "sequence inputs must contain Scenario instances only")
        batch = stack_scenarios(items, n_max=n_max)
    else:
        raise TypeError(
            f"cannot coerce {type(problem).__name__!r} — pass a Scenario, a "
            "Sequence[Scenario], a ScenarioBatch or an AdmissionWindow")
    if dtype is not None:
        batch = ScenarioBatch(scenarios=_cast_floats(batch.scenarios, dtype),
                              mask=batch.mask, n_classes=batch.n_classes)
    return batch


def _cast_floats(tree, dtype):
    """Cast every floating leaf of a pytree to ``dtype`` (the one
    dtype-coercion rule of the engine; integer/bool leaves pass through)."""
    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda leaf: (leaf.astype(dt)
                      if jnp.issubdtype(leaf.dtype, jnp.floating) else leaf),
        tree)


def _dtype_check(cfg: "SolverConfig", batch: ScenarioBatch, sol: Solution,
                 masks=None) -> Optional[dict]:
    """The ``dtype_policy="f32_checked"`` cross-check of a batched solve.

    Re-solves ``cfg.check_sample()`` evenly-spaced sample lanes in float64
    on the unfused reference path (cold start, no kernels, no mesh — the
    most conservative configuration available) and compares allocations.
    Both solves are ``eps_bar``-converged equilibria of the same game, so
    their allocations can legitimately differ by up to one stopping
    tolerance each; the check holds the per-lane relative L1 deviation to
    ``2 * cfg.eps_bar`` (plus a small absolute slack for near-zero
    allocations) and raising past it means the f32 path left the f64
    equilibrium's basin — a real precision failure, not rounding noise.

    Parameters
    ----------
    cfg : SolverConfig
        The active config (supplies ``eps_bar`` and the sample count).
    batch : ScenarioBatch
        The batch that was solved (f32 leaves under the policy).
    sol : Solution
        The f32 solution to audit.
    masks : jnp.ndarray, optional
        Lane-validity mask ((B,) bool) restricting which lanes may be
        sampled — streaming windows pass their occupancy so free slots
        are never audited.  None samples over all lanes.

    Returns
    -------
    dict or None
        ``{"lanes": [...], "max_rel": float, "bound": float}``; None when
        the config's policy does not check or no lane is eligible.

    Raises
    ------
    RuntimeError
        Naming the offending lanes when any sampled lane deviates beyond
        the bound.
    """
    k = cfg.check_sample()
    if k == 0:
        return None
    if not jax.config.jax_enable_x64:
        # Without x64 the float64 re-solve silently truncates back to f32
        # and the "check" compares the fast path against itself.
        raise RuntimeError(
            f"dtype_policy={cfg.dtype_policy!r} needs jax_enable_x64: with "
            "x64 disabled the f64 reference re-solve truncates to float32 "
            "and the cross-check can never fail")
    eligible = (np.arange(batch.batch_size) if masks is None
                else np.flatnonzero(np.asarray(masks)))
    if eligible.size == 0:
        return None
    k = min(k, eligible.size)
    pick = np.unique(np.linspace(0, eligible.size - 1, k).round().astype(int))
    lanes = [int(b) for b in eligible[pick]]

    sub = batch.take(np.asarray(lanes))
    sub64 = ScenarioBatch(
        scenarios=_cast_floats(sub.scenarios, jnp.float64),
        mask=sub.mask, n_classes=sub.n_classes)
    ref = game.solve_distributed_batch(sub64, eps_bar=cfg.eps_bar,
                                       lam=cfg.lam, max_iters=cfg.max_iters)
    r32 = jnp.asarray(sol.r)[np.asarray(lanes)].astype(jnp.float64)
    r64 = ref.r
    dev = jnp.sum(jnp.abs(r32 - r64), axis=1)
    scale = jnp.maximum(jnp.sum(jnp.abs(r64), axis=1), 1.0)
    rel = np.asarray(dev / scale)
    bound = 2.0 * cfg.eps_bar + 1e-6
    if np.any(rel > bound):
        bad = [lanes[i] for i in np.flatnonzero(rel > bound)]
        raise RuntimeError(
            f"dtype_policy={cfg.dtype_policy!r}: lanes {bad} deviate from "
            f"the f64 reference beyond {bound:.3g} relative "
            f"(worst {float(rel.max()):.3g}) — the f32 fast path is not "
            "trustworthy for this workload; use dtype_policy='f64'")
    return {"lanes": lanes, "max_rel": float(rel.max()), "bound": bound}


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------


class CapacityEngine:
    """The single entry point to the GNEP capacity-allocation stack.

    One engine = one :class:`SolverConfig` (solver knobs, kernel, mesh) +
    one :class:`Policies` bundle (flush cadence, compaction, rounding,
    cross-check).  Engines are cheap, stateless handles — all compiled
    programs live in module-level caches keyed by the config values, so
    constructing many engines costs nothing; all *mutable* state (warm
    starts, pending events) lives in the :class:`WindowSession` /
    ``AdmissionWindow`` a session wraps.

    Parameters
    ----------
    config : SolverConfig, optional
        Solver knobs + kernel + placement (defaults: the paper's).
    policies : Policies, optional
        Operational policies (defaults: round, no cross-check, flush every
        8 events, never auto-compact).
    """

    def __init__(self, config: Optional[SolverConfig] = None,
                 policies: Optional[Policies] = None):
        self.config = config if config is not None else SolverConfig()
        self.policies = policies if policies is not None else Policies()
        if self.config.residency not in ("round-trip", "resident"):
            raise ValueError(
                f"unknown residency {self.config.residency!r} — "
                "expected 'round-trip' or 'resident'")
        if self.config.residency == "resident" and self.config.mesh is None:
            raise ValueError(
                "residency='resident' needs a mesh= in the SolverConfig "
                "(repro.core.sharding.lane_mesh)")
        if (self.config.check_sample() > 0
                and self.config.residency == "resident"):
            # the resident flush donates its warm-start buffers to the
            # solve, so the f64 shadow re-solve the check needs cannot see
            # the same init — refusing keeps the check's semantics exact
            # instead of silently weakening them
            raise ValueError(
                "dtype_policy='f32_checked' is not supported with "
                "residency='resident' — use residency='round-trip' for "
                "checked f32, or dtype_policy='f64' for resident sessions")

    # ------------------------------------------------------------- one-shot
    def solve(self, problem, *, method: str = "distributed",
              check_feasible: bool = True
              ) -> Union[SolveReport, BatchSolveReport]:
        """Solve one instance or one batch of independent instances.

        Parameters
        ----------
        problem : Scenario, Sequence[Scenario], ScenarioBatch or AdmissionWindow
            A single :class:`Scenario` runs the single-instance pipeline
            (any ``method``); everything else is coerced by
            :func:`_coerce` and runs the batched engine (B lanes as one
            XLA program, sharded over ``config.mesh`` when set).
        method : str, optional
            ``"distributed"`` (Algorithm 4.1, default), ``"centralized"``
            (exact P3 water-filling) or ``"distributed-python"`` (the
            paper-faithful serial loop) — the latter two for single
            instances only.
        check_feasible : bool, optional
            Batched path: with True (default) an :class:`InfeasibleError`
            names every infeasible lane; False returns per-lane
            ``feasible`` flags instead (what-if sweeps legitimately probe
            infeasible capacity points).  The single-instance path always
            raises, as the legacy facade did.

        Returns
        -------
        SolveReport or BatchSolveReport
            Fractional (and, per the rounding policy, integer) solutions
            plus iteration counts; batched reports carry a leading B dim
            on every leaf and ``instance(b)`` trims one lane.

        Raises
        ------
        InfeasibleError
            If ``sum(r_low) > R`` or some deadline is unattainable
            (E_i >= 0) — per ``check_feasible`` on the batched path.
        ValueError
            For an unknown or unsupported ``method``.
        """
        if isinstance(problem, Scenario):
            return self._solve_single(problem, method)
        if method != "distributed":
            raise ValueError("batched solves support method='distributed' "
                             f"only, got {method!r}")
        return self._solve_batch(
            _coerce(problem, dtype=self.config.effective_dtype()),
            check_feasible)

    def _solve_single(self, scn: Scenario, method: str) -> SolveReport:
        cfg = self.config
        if cfg.effective_dtype() is not None:
            scn = _cast_floats(scn, cfg.effective_dtype())
        t0 = time.perf_counter()
        if method == "centralized":
            sol = solve_centralized(scn)
        elif method == "distributed":
            sol = game.solve_distributed(scn, eps_bar=cfg.eps_bar,
                                         lam=cfg.lam,
                                         max_iters=cfg.max_iters)
        elif method == "distributed-python":
            sol, _, _ = game.solve_distributed_python(
                scn, eps_bar=cfg.eps_bar, lam=cfg.lam,
                max_iters=cfg.max_iters)
        else:
            raise ValueError(f"unknown method {method!r}")

        if not bool(sol.feasible):
            raise InfeasibleError(
                "instance infeasible: "
                f"sum(r_low)={float(jnp.sum(scn.r_low)):.1f} "
                f"> R={float(scn.R):.1f} or some E_i >= 0")

        if cfg.check_sample() > 0 and method == "distributed":
            # single-instance flavor of _dtype_check: one f64 re-solve
            sol64 = game.solve_distributed(
                _cast_floats(scn, jnp.float64), eps_bar=cfg.eps_bar,
                lam=cfg.lam, max_iters=cfg.max_iters)
            dev = float(jnp.sum(jnp.abs(sol.r.astype(jnp.float64) - sol64.r)))
            scale = max(float(jnp.sum(jnp.abs(sol64.r))), 1.0)
            bound = 2.0 * cfg.eps_bar + 1e-6
            if dev / scale > bound:
                raise RuntimeError(
                    f"dtype_policy={cfg.dtype_policy!r}: instance deviates "
                    f"from the f64 reference beyond {bound:.3g} relative "
                    f"({dev / scale:.3g}) — use dtype_policy='f64'")

        integer_sol = (round_solution(scn, sol.r, sol.sM, sol.sR, sol.psi)
                       if self.policies.rounding.enabled else None)
        return SolveReport(method=method, fractional=sol, integer=integer_sol,
                           iters=int(sol.iters), config=cfg,
                           elapsed_s=time.perf_counter() - t0)

    def _solve_batch(self, batch: ScenarioBatch,
                     check_feasible: bool) -> BatchSolveReport:
        cfg = self.config
        t0 = time.perf_counter()
        sol = game.solve_distributed_batch(batch, eps_bar=cfg.eps_bar,
                                           lam=cfg.lam,
                                           max_iters=cfg.max_iters,
                                           sweep_fn=cfg.sweep_fn,
                                           mesh=cfg.mesh,
                                           iter_fn=cfg.iter_fn)
        if check_feasible and not bool(jnp.all(sol.feasible)):
            bad = [int(b) for b in jnp.nonzero(~sol.feasible)[0]]
            raise InfeasibleError(f"instances {bad} infeasible: "
                                  "sum(r_low) > R or some E_i >= 0")
        dtype_check = _dtype_check(cfg, batch, sol)

        integer_sol = (round_solution_batch(batch, sol.r, sol.sM, sol.sR,
                                            sol.psi)
                       if self.policies.rounding.enabled else None)
        return BatchSolveReport(method="distributed", fractional=sol,
                                integer=integer_sol, iters=sol.iters,
                                config=cfg,
                                elapsed_s=time.perf_counter() - t0,
                                mask=batch.mask, n_classes=batch.n_classes,
                                feasible=sol.feasible,
                                dtype_check=dtype_check)

    # ------------------------------------------------------------ sessions
    def open_window(self, lanes, *, n_max: Optional[int] = None,
                    growth_factor: float = 2.0,
                    quota: Optional[TenantQuota] = None) -> "WindowSession":
        """Open the runtime loop: a live window driven by this engine.

        Parameters
        ----------
        lanes : AdmissionWindow, Scenario, Sequence[Scenario] or ScenarioBatch
            An existing live window is adopted as-is (its warm-start state,
            occupancy and dirty flags are preserved — this is how the
            legacy streaming facades delegate); anything else is coerced by
            :func:`_coerce` into the initial lane set of a fresh
            :class:`~repro.core.streaming.AdmissionWindow`.
        n_max : int, optional
            Initial padded width of a fresh window (headroom avoids early
            growth repads); ignored when adopting an existing window.
        growth_factor : float, optional
            Fresh-window growth multiplier when a lane's row fills
            (ignored when adopting an existing window).
        quota : TenantQuota, optional
            Per-tenant budget the session enforces: ``offer`` refuses
            events past ``max_queued`` and ``add_lane`` refuses lanes past
            ``max_lanes`` (both with :class:`QuotaExceededError`).  The
            initial lane count must already fit the lane budget.

        Returns
        -------
        WindowSession
            The session; all solver/policy behavior comes from this
            engine's ``config`` and ``policies``.
        """
        if isinstance(lanes, AdmissionWindow):
            return WindowSession(self, lanes, quota=quota)
        batch = _coerce(lanes, dtype=self.config.effective_dtype())
        scns = [batch.instance(b) for b in range(batch.batch_size)]
        window = AdmissionWindow(scns, n_max=n_max or batch.n_max,
                                 growth_factor=growth_factor)
        return WindowSession(self, window, quota=quota)

    # ----------------------------------------------------------- internals
    def _solve_window(self, window: AdmissionWindow) -> WindowSolveReport:
        """Warm-started incremental re-solve of a live window (the streaming
        mechanism: only dirty lanes iterate, clean lanes freeze at their
        stored equilibrium; numerically equivalent to a cold re-solve).
        Dispatches on residency: a device-resident window (or a
        ``residency='resident'`` config, which makes the window resident on
        first use) takes the zero-resharding resident path."""
        cfg = self.config
        with span("engine.solve"):
            if not window.is_resident and cfg.residency == "resident":
                window.make_resident(cfg.mesh)
            if window.is_resident:
                if cfg.mesh is not None and cfg.mesh != window.resident_mesh:
                    raise ValueError(
                        "window is resident on a different mesh than the "
                        "engine's config.mesh — release_resident or match "
                        "them")
                return self._solve_window_resident(window)
            return self._solve_window_roundtrip(window)

    def _solve_window_roundtrip(self,
                                window: AdmissionWindow) -> WindowSolveReport:
        """The classic flush: host-side warm start, per-solve mesh placement
        (when ``config.mesh`` is set), host-trimmed result."""
        cfg = self.config
        t0 = time.perf_counter()
        batch = window.batch
        init = window.warm_start()
        resolved = np.asarray(init.active).copy()

        sol = game.solve_distributed_batch(batch, eps_bar=cfg.eps_bar,
                                           lam=cfg.lam,
                                           max_iters=cfg.max_iters,
                                           sweep_fn=cfg.sweep_fn, init=init,
                                           mesh=cfg.mesh, iter_fn=cfg.iter_fn)
        window.commit(sol.r, sol.aux, sol.iters)
        dtype_check = _dtype_check(cfg, batch, sol,
                                   masks=np.asarray(batch.mask).any(axis=1))
        return self._window_report(window, batch, sol, resolved, t0,
                                   dtype_check=dtype_check)

    def _solve_window_resident(self,
                               window: AdmissionWindow) -> WindowSolveReport:
        """The resident flush: scenario leaves, mask mirror and warm-start
        state already live lane-sharded on the window's mesh, the init is
        built on-device and its buffers donated to the solve — zero
        per-flush host->mesh resharding (the tentpole of the
        device-resident session design; see docs/ARCHITECTURE.md)."""
        cfg = self.config
        t0 = time.perf_counter()
        rbatch = window.resident_batch()
        init, resolved = window.resident_warm_start(rbatch)
        sol_p = sharding.solve_resident_batch(
            rbatch, window.resident_mesh, eps_bar=cfg.eps_bar, lam=cfg.lam,
            max_iters=cfg.max_iters, sweep_fn=cfg.sweep_fn, init=init,
            iter_fn=cfg.iter_fn)
        del init                  # donated: unusable after the solve
        window.commit(sol_p.r, sol_p.aux, sol_p.iters)
        b = window.batch_size
        sol = (sol_p if rbatch.batch_size == b
               else jax.tree_util.tree_map(lambda leaf: leaf[:b], sol_p))
        # the report's batch view is the logical host mirror — same mask
        # snapshot recipe as the round-trip path, so reports from the two
        # paths are structurally identical (tests/test_resident.py asserts
        # bit-equality)
        return self._window_report(window, window.batch, sol, resolved, t0)

    def _window_report(self, window: AdmissionWindow, batch: ScenarioBatch,
                       sol, resolved: np.ndarray, t0: float,
                       dtype_check: Optional[dict] = None
                       ) -> WindowSolveReport:
        """Shared tail of both flush paths: centralized cross-check,
        Algorithm 4.2 rounding, report assembly — all over the LOGICAL
        lane count."""
        cfg, pol = self.config, self.policies
        gap = None
        if pol.cross_check.enabled:
            # The exact (P3) optimum of a lane only changes when its
            # scenario does, so recompute just the stale lanes and serve
            # the rest from the window's memo.  Per-lane single-instance
            # solves keep the shapes (n_max,) regardless of how many lanes
            # are stale — one compiled program per window width, never a
            # retrace per stale count the way a ragged sub-batch gather
            # would.
            stale = np.flatnonzero(window.baseline_stale)
            for b in stale:
                lane = jax.tree_util.tree_map(lambda l: l[b], batch.scenarios)
                window.baseline_totals[b] = float(
                    solve_centralized(lane, mask=batch.mask[b]).total)
            window.baseline_stale[stale] = False
            cent_total = jnp.asarray(window.baseline_totals, sol.total.dtype)
            scale = jnp.maximum(jnp.abs(cent_total), 1.0)
            gap = (sol.total - cent_total) / scale
            undercut = ((sol.total < cent_total - pol.cross_check.atol)
                        & sol.feasible)
            if bool(jnp.any(undercut)):
                bad = [int(b) for b in jnp.nonzero(undercut)[0]]
                raise RuntimeError(
                    f"lanes {bad}: GNEP total beats the exact (P3) optimum "
                    "— solver inconsistency (check mask/padding invariants)")

        integer_sol = (round_solution_batch(batch, sol.r, sol.sM, sol.sR,
                                            sol.psi)
                       if pol.rounding.enabled else None)
        return WindowSolveReport(method="streaming", fractional=sol,
                                 integer=integer_sol, iters=sol.iters,
                                 config=cfg,
                                 elapsed_s=time.perf_counter() - t0,
                                 mask=batch.mask, n_classes=batch.n_classes,
                                 feasible=sol.feasible, resolved=resolved,
                                 centralized_gap=gap,
                                 dtype_check=dtype_check)


class WindowSession:
    """The paper's runtime loop as a session: events in, equilibria out.

    Wraps a live :class:`~repro.core.streaming.AdmissionWindow` and owns
    everything the legacy facades made the caller thread by hand: the
    event buffer and its :class:`~repro.core.streaming.FlushPolicy` (incl.
    deadline-aware immediate flushes), the warm-start state carried between
    re-solves, mesh placement, compaction policy, rounding and the
    centralized cross-check.  Per-lane ``feasible`` flags report infeasible
    transients without raising (arrival bursts legitimately overload a
    window until load is shed).

    Obtain sessions from :meth:`CapacityEngine.open_window`.

    Parameters
    ----------
    engine : CapacityEngine
        Supplies ``config`` (solver knobs, kernel, mesh) and ``policies``.
    window : AdmissionWindow
        The live window; mutated by ``apply``/``flush``/lane operations.
    quota : TenantQuota, optional
        Per-tenant budget; ``offer`` and ``add_lane`` enforce it with
        :class:`QuotaExceededError`.  ``None`` is unlimited.
    """

    def __init__(self, engine: CapacityEngine, window: AdmissionWindow,
                 quota: Optional[TenantQuota] = None):
        if (quota is not None
                and not quota.admits_lane(window.batch_size - 1)):
            raise QuotaExceededError(
                f"window opens with {window.batch_size} lanes, quota "
                f"allows {quota.max_lanes}")
        self.engine = engine
        self.window = window
        self.quota = quota
        self._pending: List[StreamEvent] = []
        self.flushes = 0
        self.events_folded = 0
        self.last_slots: List[Optional[int]] = []
        self._last_report: Optional[WindowSolveReport] = None

    # ------------------------------------------------------------- queries
    @property
    def pending(self):
        """Buffered, not-yet-applied events (application order)."""
        return tuple(self._pending)

    @property
    def dirty_lanes(self) -> Set[int]:
        """Lanes the next flush will re-solve: window-dirty | buffered."""
        return (set(int(b) for b in np.flatnonzero(self.window.dirty))
                | {ev.lane for ev in self._pending})

    # --------------------------------------------------------------- verbs
    def solve(self) -> WindowSolveReport:
        """Warm-started incremental re-solve of the window's current state.

        Only lanes dirtied since the last equilibrium iterate Algorithm 4.1
        (restarting from the paper's cold init so they reproduce the cold
        trajectory exactly); clean lanes are frozen at zero solver cost.
        Buffered events are NOT applied — use :meth:`flush` for that.

        Returns
        -------
        WindowSolveReport
            Batch result over all lanes plus ``resolved`` /
            ``centralized_gap`` bookkeeping.
        """
        return self.engine._solve_window(self.window)

    def apply(self, *events: StreamEvent) -> Optional[WindowSolveReport]:
        """Buffer events; flush automatically when the policy demands it.

        Each event is checked against the engine's flush policy: an
        SLA-critical event (per ``FlushPolicy.deadline``) or a fired
        count / dirty-fraction trigger causes an immediate :meth:`flush`
        — bulk events keep coalescing until then.

        Parameters
        ----------
        *events : StreamEvent
            ClassArrival / ClassDeparture / SLAEdit / CapacityChange, in
            application order (validated atomically at flush).

        Returns
        -------
        WindowSolveReport or None
            The report of the LAST policy-triggered flush, or None when
            everything is still buffered.
        """
        policy = self.engine.policies.flush
        report = None
        for ev in events:
            self._pending.append(ev)
            if self._policy_fires(policy, ev):
                report = self.flush()
        return report

    def _policy_fires(self, policy: FlushPolicy, ev: StreamEvent) -> bool:
        """One buffered event's flush decision (dirty-lane accounting is
        skipped unless the policy actually has a dirty-fraction trigger —
        it costs a host scan per event on the dispatch-bound path)."""
        if policy.is_critical(ev, self.window):
            return True
        n_dirty = (len(self.dirty_lanes)
                   if policy.max_dirty_fraction is not None else 0)
        return policy.should_flush(n_events=len(self._pending),
                                   n_dirty=n_dirty,
                                   batch_size=self.window.batch_size)

    def offer(self, event: StreamEvent) -> bool:
        """Buffer one event WITHOUT flushing; report whether a flush is due.

        The external-scheduler hook: :meth:`apply` decides *and executes*
        flushes inline, which is right for a single session but wrong for a
        daemon multiplexing many sessions — there the flush *order* across
        sessions is a scheduling decision (``repro.serving.allocd`` flushes
        the session with the tightest SLA slack first).  ``offer`` runs
        exactly the flush-policy check :meth:`apply` runs (so flush
        *boundaries* stay bit-identical to an inline replay) but leaves the
        flush to the caller.  Once ``offer`` returns True, do not offer the
        session further events until :meth:`flush` has run — interleaving
        would move the boundary and break replay conformance.

        Parameters
        ----------
        event : StreamEvent
            The event to buffer (validated atomically at flush).

        Returns
        -------
        bool
            True when the engine's flush policy demands a flush now —
            including SLA-critical events under a deadline-aware policy.

        Raises
        ------
        QuotaExceededError
            When the session carries a :class:`TenantQuota` and the buffer
            already holds ``max_queued`` events.  Schedulers that meter
            their own queues against the quota (the admission daemon does)
            never trip this; it is the backstop against unbounded buffer
            growth under a flush policy that never fires.
        """
        if (self.quota is not None
                and not self.quota.admits_event(len(self._pending))):
            raise QuotaExceededError(
                f"session buffer holds {len(self._pending)} events, quota "
                f"allows {self.quota.max_queued}")
        self._pending.append(event)
        return self._policy_fires(self.engine.policies.flush, event)

    def pending_slack(self) -> float:
        """Tightest SLA slack [s] carried by the buffered events.

        The cross-session scheduling key of ``repro.serving.allocd``: among
        sessions due to flush, the one whose tightest deadline expires
        soonest flushes first.  Slack of one event is ``-E`` (``E = C - D``
        is negative while the deadline is attainable) taken from a
        :class:`~repro.core.types.ClassArrival`'s params or an
        :class:`~repro.core.types.SLAEdit`'s updates; events that carry no
        deadline (departures, capacity changes, E-less edits) contribute
        nothing.

        Returns
        -------
        float
            ``min(-E)`` over deadline-carrying buffered events, ``inf``
            when there are none (flush-order ties break by fairness, not
            urgency).
        """
        slack = np.inf
        for ev in self._pending:
            E = None
            if isinstance(ev, ClassArrival):
                E = ev.params.get("E")
            elif isinstance(ev, SLAEdit):
                E = ev.updates.get("E")
            if E is not None:
                slack = min(slack, -float(E))
        return slack

    def drain(self) -> List[Optional[int]]:
        """Fold every buffered event into the window WITHOUT re-solving.

        One ``AdmissionWindow.apply_epoch`` however many events are
        pending, one event included: one upload of the packed epoch and
        one compiled commit program, with no read back from the device.
        The window is left dirty for the next :meth:`solve` /
        :meth:`flush`.  Drivers that need arrival slot grants before
        deciding further events (the fleet layer does) call this directly.

        Returns
        -------
        list of (int or None)
            Per-event slot grants (arrivals) in buffer order — also kept
            on ``last_slots``; empty when nothing was pending.
        """
        if not self._pending:
            return []
        slots = self.window.apply_epoch(self._pending)
        self.events_folded += len(self._pending)
        self._pending = []
        self.last_slots = slots
        return slots

    def discard_pending(self) -> Tuple[StreamEvent, ...]:
        """Drop every buffered event without folding it into the window.

        The abort hook for external schedulers: an aborting daemon (or a
        driver whose epoch failed ``apply_epoch`` validation) must leave
        the session at its last *flushed* state — partially-buffered
        epochs are discarded rather than half-applied, so the session's
        flush-boundary history stays a prefix of the full-trace replay.
        The window itself is untouched (state, dirty flags, counters).

        Returns
        -------
        tuple of StreamEvent
            The dropped events, in the order they were buffered (callers
            may re-queue, log or fail them).
        """
        dropped = tuple(self._pending)
        self._pending = []
        return dropped

    def flush(self) -> WindowSolveReport:
        """Apply buffered events, run policy compaction, re-solve once.

        The coalesced cadence step: ONE window update folds the whole
        buffer, the compaction policy may re-pack a sparse window (the
        report's ``slot_map`` records the re-layout), and ONE warm-started
        re-solve re-equilibrates the union of dirtied lanes.  An empty
        flush on a clean, already-solved, geometry-unchanged window is a
        true no-op: it echoes the previous flush's report (``slot_map``
        cleared — no compaction happened NOW) without any solve dispatch;
        the daemon's drain path hits this on every idle session, and
        ``flushes`` / ``events_folded`` do not advance.

        Returns
        -------
        WindowSolveReport
            Numerically equivalent to having re-solved after every single
            event (the last per-event solve of the epoch; proven in
            ``tests/test_coalescing.py``).
        """
        if (not self._pending and self._last_report is not None
                and self.window.state is not None
                and not self.window.dirty.any()
                and np.array_equal(np.asarray(self._last_report.mask),
                                   self.window._mask)):
            # slot_map describes the PREVIOUS flush's compaction — this
            # no-op flush performed none, so the echo must not carry it
            return replace(self._last_report, slot_map=None)
        with span("session.apply"):
            self.drain()
            report_map = None
            comp = self.engine.policies.compaction
            if (comp.occupancy is not None
                    and self.window.occupancy < comp.occupancy):
                counts = self.window.n_classes
                widest = max(int(counts.max()), 1)
                target = max(int(np.ceil(comp.headroom * widest)), widest)
                report_map = self.window.compact(n_max=target)
        report = self.engine._solve_window(self.window)
        report.slot_map = report_map
        self.flushes += 1
        self._last_report = report
        return report

    def stream(self, events: Iterable[StreamEvent]
               ) -> Iterator[WindowSolveReport]:
        """Replay an event stream in policy-coalesced re-solve epochs.

        The generator form of :meth:`apply`: events accumulate until the
        flush policy triggers (count, dirty fraction, or an SLA-critical
        event), then one coalesced flush yields its report.  A trailing
        partial epoch is flushed after the stream ends, so consuming the
        generator always leaves the window clean and solved.

        Parameters
        ----------
        events : iterable of StreamEvent
            The event stream, in application order.  May be a lazy
            iterator — epochs form as events arrive.

        Yields
        ------
        WindowSolveReport
            One per flush, in stream order.
        """
        for ev in events:
            report = self.apply(ev)
            if report is not None:
                yield report
        if self._pending:
            yield self.flush()

    # ----------------------------------------------------- window geometry
    def add_lane(self, scn: Optional[Scenario] = None, *,
                 R: Optional[float] = None,
                 rho_bar: Optional[float] = None) -> int:
        """Append one lane (a new cluster / fleet joining the window).

        Buffered events are drained first (lane geometry changes only at
        flush boundaries); the new lane starts dirty/never-solved, so the
        next solve iterates exactly it.

        Parameters
        ----------
        scn : Scenario, optional
            Initial classes of the new lane; ``None`` admits an empty lane.
        R : float, optional
            Lane capacity, required (with ``rho_bar``) when ``scn`` is None.
        rho_bar : float, optional
            Lane unit chip cost, required (with ``R``) when ``scn`` is None.

        Returns
        -------
        int
            The new lane's index.

        Raises
        ------
        QuotaExceededError
            When the session's :class:`TenantQuota` caps ``max_lanes`` and
            the window is already at it.
        """
        if (self.quota is not None
                and not self.quota.admits_lane(self.window.batch_size)):
            raise QuotaExceededError(
                f"window already holds {self.window.batch_size} lanes, "
                f"quota allows {self.quota.max_lanes}")
        self.drain()
        return self.window.add_lane(scn, R=R, rho_bar=rho_bar)

    def remove_lane(self, lane: int) -> None:
        """Drop ``lane`` and shrink B by one (buffered events drain first).

        Parameters
        ----------
        lane : int
            Lane to remove; higher lanes shift down by one and clean lanes
            stay frozen across the shrink.
        """
        self.drain()
        self.window.remove_lane(lane)

    def compact(self, *, n_max: Optional[int] = None) -> np.ndarray:
        """Re-pack the window now (buffered events drain first).

        Parameters
        ----------
        n_max : int, optional
            Target padded width (default: minimal); see
            ``AdmissionWindow.compact``.

        Returns
        -------
        np.ndarray
            (B, old_n_max) old-slot -> new-slot map (-1 where empty).
        """
        self.drain()
        return self.window.compact(n_max=n_max)


# --------------------------------------------------------------------------
# Legacy plumbing (no DeprecationWarning: mechanism, not facade)
# --------------------------------------------------------------------------


def _legacy_solve_window(window: AdmissionWindow, *, eps_bar: float = 0.03,
                         lam: float = 0.05, max_iters: int = 200,
                         integer: bool = True, sweep_fn=None, mesh=None,
                         cross_check: bool = False,
                         cross_check_atol: float = 1e-6) -> WindowSolveReport:
    """kwargs -> (config, policies) adapter used by the deprecated facades
    and ``EventEpoch.flush`` so in-repo mechanism never routes through a
    warning-emitting shim."""
    eng = CapacityEngine(
        SolverConfig(eps_bar=eps_bar, lam=lam, max_iters=max_iters,
                     sweep_fn=sweep_fn, mesh=mesh),
        Policies(rounding=RoundingPolicy(integer),
                 cross_check=CrossCheckPolicy(cross_check, cross_check_atol)))
    return eng._solve_window(window)
