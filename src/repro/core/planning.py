"""Fleet-scale capacity planner: design-space exploration over what-if batches.

The paper's allocator answers "how many chips does each class get *right
now*"; this module builds the system D-SPACE4Cloud (PAPERS.md) shows on top
of exactly such an allocator — a design-tool loop that sweeps cluster size /
VM tier / deadline tightness / penalty scaling and returns the cheapest
feasible design:

* :class:`PlanSpec` declares the fleet design space (axes) plus the workload
  it is sized for — one of the shared trace profiles of
  :mod:`repro.core.traces`, so what-if planning and the always-on admission
  daemon are driven by the same workloads;
* :func:`generate_grid` expands the spec into a deterministic, seeded list
  of :class:`Candidate` design points, each carrying a fully derived
  :class:`~repro.core.types.Scenario` (the deadline axis is the innermost
  grid dimension, so adjacent candidates differ only in deadline tightness);
* :func:`solve_plan` packs candidates into fixed-width, inert-lane-padded
  :class:`~repro.core.types.ScenarioBatch` chunks and pushes them through
  the existing :class:`~repro.core.engine.CapacityEngine` batch path
  (mesh-sharded when the config carries one).  Lanes are independent and
  padding is solver-inert, so the chunked results are **bit-equal** to one
  direct ``CapacityEngine.solve`` over all candidates
  (``tests/test_planning.py`` proves it, sharded and unsharded).  An
  opt-in warm-start mode seeds each deadline step's allocation from the
  previous step's equilibrium (bids restart cold, so the Alg. 4.1 iterate
  trajectory is preserved and only the stopping time can differ);
* :class:`PlanReport` reduces the per-candidate solutions into the paper's
  objective decomposition (power cost vs rejection penalty, per-lane
  feasibility = "deadline attainable under this design"), with Pareto
  frontier extraction and a cheapest-feasible-design query.

CLI: ``python -m repro.launch.plan``; benchmark: ``python3 bench/run.py
--workload plan-1000c`` (candidates/s on the chip, PERF.md); operator
guide: ``docs/OPERATIONS.md`` "Capacity planning".
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import game, sharding
from repro.core.engine import (CapacityEngine, Policies, RoundingPolicy,
                               SolverConfig, _cast_floats)
from repro.core.profiles import sample_class_params
from repro.core.traces import ARRIVAL_PROFILES
from repro.core.types import Scenario, ScenarioBatch, derive, pad_scenario
from repro.spans import span
from repro.utils import fdtype


@dataclass(frozen=True)
class VMTier:
    """One VM/chip SKU the planner may build the cluster from.

    Attributes
    ----------
    name : str
        SKU label (appears in candidate coordinates and reports).
    slots : float
        Slot multiplier over the workload's per-VM baseline: candidate
        scenarios scale their per-class ``cM`` / ``cR`` by it (a
        ``slots=2`` tier packs twice the map and reduce slots per VM).
    price : float
        Unit-time cost of one VM of this tier [cents] — the candidate's
        ``rho_bar``, so tier choice trades power cost against the smaller
        per-job chip share ``K`` that more slots buy.
    """
    name: str
    slots: float
    price: float


@dataclass(frozen=True)
class PlanSpec:
    """A fleet design space plus the workload it is sized for.

    The four axes (``cluster_sizes`` x ``vm_tiers`` x ``penalty_scales`` x
    ``deadline_scales``) expand into ``len(cluster_sizes) * len(vm_tiers) *
    len(penalty_scales) * len(deadline_scales)`` candidates;
    :func:`generate_grid` orders them with the deadline axis innermost.
    The workload half (``profile`` / ``rate`` / ``trace_events`` /
    ``n_classes``) shapes the per-class demand mix: a trace from
    :data:`repro.core.traces.ARRIVAL_PROFILES` is histogrammed into
    ``n_classes`` equal time windows and the per-window load modulates each
    class's SLA concurrency, so a bursty workload is planned against a
    skewed demand mix while a steady one is planned against a flat mix.

    Attributes
    ----------
    n_classes : int
        Job classes per candidate scenario (base parameters follow the
        paper's Table 5/6 design via
        :func:`repro.core.profiles.sample_class_params`).
    profile : str
        Workload-trace profile name (a :data:`ARRIVAL_PROFILES` key).
    rate : float
        Mean arrival rate [events/s] of the sizing trace.
    trace_events : int
        Events in the sizing trace (more events -> smoother demand mix).
    cluster_sizes : tuple of float
        Candidate cluster capacities R (number of VMs/chips).
    vm_tiers : tuple of VMTier
        Candidate VM SKUs (slot multiplier + unit price).
    deadline_scales : tuple of float
        Deadline-tightness multipliers on D_i (< 1 tightens, paper
        Sec. 5.2.2); the innermost grid axis, which is what the
        warm-start mode exploits.
    penalty_scales : tuple of float
        Multipliers on the per-class rejection penalty ``m``.
    seed : int
        Seed for both the class-parameter draws and the sizing trace; the
        grid is a pure function of the spec (same spec -> bit-identical
        candidates).
    """
    n_classes: int = 4
    profile: str = "poisson"
    rate: float = 50.0
    trace_events: int = 512
    cluster_sizes: Tuple[float, ...] = (1500.0, 3000.0, 6000.0)
    vm_tiers: Tuple[VMTier, ...] = (VMTier("small", 1.0, 6.0),
                                    VMTier("large", 2.0, 10.0))
    deadline_scales: Tuple[float, ...] = (0.8, 1.0, 1.2)
    penalty_scales: Tuple[float, ...] = (1.0,)
    seed: int = 0

    @property
    def grid_shape(self) -> Tuple[int, int, int, int]:
        """Axis lengths in grid order: (clusters, tiers, penalties,
        deadlines)."""
        return (len(self.cluster_sizes), len(self.vm_tiers),
                len(self.penalty_scales), len(self.deadline_scales))

    @property
    def n_candidates(self) -> int:
        """Total design points the spec expands into."""
        n = 1
        for axis in self.grid_shape:
            n *= axis
        return n


@dataclass(frozen=True)
class Candidate:
    """One design point of an expanded :class:`PlanSpec` grid.

    Attributes
    ----------
    index : int
        Position in the grid's candidate order (deadline axis innermost).
    coords : dict
        The design coordinates that produced the scenario:
        ``cluster_size``, ``tier`` (name), ``penalty_scale``,
        ``deadline_scale``.
    scenario : repro.core.types.Scenario
        The fully derived allocation instance for this design point.
    """
    index: int
    coords: Dict[str, object]
    scenario: Scenario


def _trace_weights(spec: PlanSpec) -> np.ndarray:
    """Per-class demand weights from the spec's sizing trace.

    The trace is histogrammed into ``n_classes`` equal time windows; each
    window's share of the events, normalized to mean 1 and floored at 0.25
    (a quiet window still hosts a real class), becomes its class's demand
    weight.  A steady profile yields a flat mix, a bursty one a skewed mix.

    Parameters
    ----------
    spec : PlanSpec
        Supplies profile, seed, trace_events, rate and n_classes.

    Returns
    -------
    numpy.ndarray
        (n_classes,) float weights, mean ~1, min 0.25.
    """
    times = ARRIVAL_PROFILES[spec.profile](spec.seed, spec.trace_events,
                                           spec.rate)
    edges = np.linspace(0.0, float(times[-1]), spec.n_classes + 1)
    counts, _ = np.histogram(np.asarray(times), bins=edges)
    mean = max(float(counts.mean()), 1e-12)
    return np.maximum(counts / mean, 0.25)


def generate_grid(spec: PlanSpec) -> List[Candidate]:
    """Expand ``spec`` into its deterministic candidate list.

    Base class parameters follow the paper's Table 5/6 design, drawn once
    per (class, deadline_scale) with a per-class fold of ``spec.seed`` —
    the SAME key at every deadline scale, so two candidates differing only
    in ``deadline_scale`` share every draw and differ only through the
    scaled deadline (this is what makes warm-starting along the deadline
    axis meaningful).  The sizing trace's demand weights modulate each
    class's SLA concurrency (``H_up``, with ``H_low = max(floor(0.8 *
    H_up), 1)`` per Table 6); the tier scales ``cM`` / ``cR`` by its slot
    count and prices the candidate's ``rho_bar``; the penalty scale
    multiplies ``m``.

    Candidate order: ``cluster_sizes`` (outermost) x ``vm_tiers`` x
    ``penalty_scales`` x ``deadline_scales`` (innermost), so
    ``index = (((ci * T) + ti) * P + pi) * D + di``.

    Parameters
    ----------
    spec : PlanSpec
        The design space; any empty axis yields an empty grid.

    Returns
    -------
    list of Candidate
        ``spec.n_candidates`` design points with derived scenarios.

    Raises
    ------
    ValueError
        Unknown ``spec.profile``, or non-positive ``n_classes`` /
        ``trace_events``.
    """
    if spec.profile not in ARRIVAL_PROFILES:
        raise ValueError(f"unknown profile {spec.profile!r} — expected one "
                         f"of {sorted(ARRIVAL_PROFILES)}")
    if spec.n_classes < 1:
        raise ValueError(f"n_classes={spec.n_classes} must be >= 1")
    if spec.trace_events < 1:
        raise ValueError(f"trace_events={spec.trace_events} must be >= 1")
    if spec.n_candidates == 0:
        return []

    dt = fdtype()
    w = _trace_weights(spec)
    key = jax.random.PRNGKey(spec.seed)
    # one draw per (deadline scale, class); the same fold at every scale
    # keeps the cross-scale draws identical (only D scales)
    base = {
        d: [sample_class_params(jax.random.fold_in(key, i),
                                deadline_scale=float(d))
            for i in range(spec.n_classes)]
        for d in spec.deadline_scales
    }

    candidates: List[Candidate] = []
    idx = 0
    for R in spec.cluster_sizes:
        for tier in spec.vm_tiers:
            for pen in spec.penalty_scales:
                for d in spec.deadline_scales:
                    cols = base[d]
                    H_up = np.asarray(
                        [max(round(p["H_up"] * w[i]), 1.0)
                         for i, p in enumerate(cols)], dt)
                    H_low = np.maximum(np.floor(0.8 * H_up), 1.0)
                    scn = derive(
                        A=np.asarray([p["A"] for p in cols], dt),
                        B=np.asarray([p["B"] for p in cols], dt),
                        E=np.asarray([p["E"] for p in cols], dt),
                        cM=np.asarray([p["cM"] * tier.slots for p in cols],
                                      dt),
                        cR=np.asarray([p["cR"] * tier.slots for p in cols],
                                      dt),
                        H_up=H_up, H_low=H_low,
                        m=np.asarray([p["m"] * pen for p in cols], dt),
                        rho_up=np.asarray([p["rho_up"] for p in cols], dt),
                        R=float(R), rho_bar=float(tier.price))
                    coords = {"cluster_size": float(R), "tier": tier.name,
                              "penalty_scale": float(pen),
                              "deadline_scale": float(d)}
                    candidates.append(Candidate(idx, coords, scn))
                    idx += 1
    return candidates


@dataclass
class PlanReport:
    """Per-candidate solutions of a plan solve, plus frontier queries.

    Every array is host-side numpy with one row per candidate, in grid
    order.  ``cost`` / ``penalty`` / ``total`` are the paper's objective
    decomposition (P2a: power cost ``rho_bar * sum r`` + rejection penalty
    ``sum alpha * psi - beta``); ``feasible`` is the per-design
    deadline-attainability flag (``sum(r_low) <= R`` and all ``E_i < 0``)
    — an infeasible design point is a legitimate probe result, not an
    error.

    Attributes
    ----------
    candidates : list of Candidate
        The solved design points (grid order).
    cost : numpy.ndarray
        (B,) power cost per candidate.
    penalty : numpy.ndarray
        (B,) rejection penalty per candidate.
    total : numpy.ndarray
        (B,) objective total (cost + penalty).
    r : numpy.ndarray
        (B, n_max) equilibrium chip allocation per candidate.
    iters : numpy.ndarray
        (B,) Algorithm 4.1 iterations per candidate.
    feasible : numpy.ndarray
        (B,) bool deadline-attainability per candidate.
    config : SolverConfig
        The solver config the plan ran under.
    chunk : int
        Lane width candidates were packed into.
    n_chunks : int
        Solve dispatches the plan took.
    warm_start : bool
        Whether the deadline-axis warm-start mode ran.
    elapsed_s : float
        Host wall-clock of the whole plan solve.
    """
    candidates: List[Candidate]
    cost: np.ndarray
    penalty: np.ndarray
    total: np.ndarray
    r: np.ndarray
    iters: np.ndarray
    feasible: np.ndarray
    config: SolverConfig
    chunk: int
    n_chunks: int
    warm_start: bool
    elapsed_s: float = 0.0

    @property
    def n_candidates(self) -> int:
        """Number of solved design points."""
        return len(self.candidates)

    def pareto_frontier(self) -> np.ndarray:
        """Indices of the feasible (cost, penalty) Pareto frontier.

        A feasible candidate is on the frontier iff no other feasible
        candidate weakly dominates it (cost <= and penalty <=, one
        strictly); of exact (cost, penalty) duplicates only the lowest
        index survives.  The sweep sorts by (cost, penalty, index) and
        keeps strict penalty improvements, so the returned indices have
        strictly increasing cost and strictly decreasing penalty.

        Returns
        -------
        numpy.ndarray
            Frontier candidate indices, sorted by increasing cost; empty
            when no candidate is feasible.
        """
        feas = np.flatnonzero(self.feasible)
        if feas.size == 0:
            return np.empty(0, dtype=int)
        order = feas[np.lexsort((feas, self.penalty[feas], self.cost[feas]))]
        front: List[int] = []
        best_pen = np.inf
        for i in order:
            if self.penalty[i] < best_pen:
                front.append(int(i))
                best_pen = self.penalty[i]
        return np.asarray(front, dtype=int)

    def cheapest_feasible(self, max_penalty: Optional[float] = None
                          ) -> Optional[int]:
        """The D-SPACE4Cloud query: cheapest design meeting every deadline.

        Parameters
        ----------
        max_penalty : float, optional
            Also require the candidate's rejection penalty to stay at or
            under this budget; ``None`` places no penalty constraint.

        Returns
        -------
        int or None
            Index of the minimum-cost feasible candidate (ties broken by
            lower penalty, then lower index); ``None`` when nothing in the
            space qualifies.
        """
        ok = self.feasible.astype(bool).copy()
        if max_penalty is not None:
            ok &= self.penalty <= max_penalty
        idx = np.flatnonzero(ok)
        if idx.size == 0:
            return None
        order = np.lexsort((idx, self.penalty[idx], self.cost[idx]))
        return int(idx[order[0]])

    def point(self, i: int) -> Dict[str, object]:
        """One candidate's coordinates + solved metrics as a flat dict.

        Parameters
        ----------
        i : int
            Candidate index.

        Returns
        -------
        dict
            ``index``, the design ``coords``, and ``cost`` / ``penalty`` /
            ``total`` / ``feasible`` / ``iters``.
        """
        return {"index": int(i), **self.candidates[i].coords,
                "cost": float(self.cost[i]),
                "penalty": float(self.penalty[i]),
                "total": float(self.total[i]),
                "feasible": bool(self.feasible[i]),
                "iters": int(self.iters[i])}

    def to_json(self) -> Dict[str, object]:
        """JSON-serializable summary (the ``--json`` payload of the CLI).

        Returns
        -------
        dict
            Candidate/feasible counts, solver provenance, the frontier
            points and the cheapest feasible design (``None`` when the
            space holds no feasible point).
        """
        cheapest = self.cheapest_feasible()
        return {
            "n_candidates": self.n_candidates,
            "n_feasible": int(np.count_nonzero(self.feasible)),
            "chunk": self.chunk, "n_chunks": self.n_chunks,
            "warm_start": self.warm_start,
            "elapsed_s": self.elapsed_s,
            "solver_config": self.config.fingerprint(),
            "frontier": [self.point(i) for i in self.pareto_frontier()],
            "cheapest_feasible": (None if cheapest is None
                                  else self.point(cheapest)),
        }


def _empty_report(cfg: SolverConfig, chunk: int,
                  warm_start: bool) -> PlanReport:
    """The zero-candidate :class:`PlanReport` (empty design space)."""
    z = np.empty(0)
    return PlanReport(candidates=[], cost=z, penalty=z, total=z,
                      r=np.empty((0, 0)), iters=np.empty(0, dtype=int),
                      feasible=np.empty(0, dtype=bool), config=cfg,
                      chunk=chunk, n_chunks=0, warm_start=warm_start)


def _chunk_targets(chunk: int, cfg: SolverConfig) -> int:
    """Padded lane width of every solve dispatch: ``chunk``, rounded up to
    the mesh's lane multiple when the config shards."""
    if cfg.mesh is None:
        return chunk
    return sharding.padded_lane_count(chunk, cfg.mesh.devices.size)


@functools.partial(jax.jit, static_argnames="dtype")
def _stack_lanes(leaves: Tuple[jnp.ndarray, ...], *, dtype) -> jnp.ndarray:
    """One field of a chunk's candidates stacked along a new lane axis and
    cast to ``dtype`` when floating (``None`` keeps it), in one compiled
    program.

    A program per field, not per chunk: on a TPU a program's set-up
    (trace, lowering, compile or load) grows with its inputs, and one
    program over every field of a chunk cost more set-up than all of its
    chunks' stacking saved (PERF.md §6).  Fields of one shape and dtype
    share a program.
    """
    out = jnp.stack(leaves)
    return out if dtype is None else _cast_floats(out, dtype)


def _stack_chunk(part: Sequence[Candidate], n_max: int, target: int, dtype,
                 mesh=None) -> Tuple[ScenarioBatch, int]:
    """One chunk's solver input: its candidates' scenarios stacked to
    ``n_max`` classes, cast to ``dtype`` (``None`` keeps theirs),
    inert-lane padded to ``target`` lanes and, with a ``mesh``, placed
    with ``sharding.lane_sharding(mesh)``; returned with its real lane
    count.

    Bit for bit what ``stack_scenarios`` -> ``sharding.pad_batch_lanes``
    -> ``_cast_floats`` give, without their per-candidate dispatches:
    candidates narrower than ``n_max`` are class-padded by
    ``pad_scenario`` (one device read of ``rho_bar`` each; the span's
    ``ragged`` counts them), each field is one :func:`_stack_lanes` call,
    and ``mask`` / ``n_classes`` come from the host-known class counts.
    Sharded, one ``device_put`` of the stacked batch places it, which
    makes the solver's own placement a no-op (an output sharded inside
    the program would replicate every input leaf to every device).
    """
    ns = np.asarray([c.scenario.n for c in part])
    ragged = int(np.count_nonzero(ns < n_max))
    with span("plan.stack", ragged=ragged):
        scns = [c.scenario if n == n_max else pad_scenario(c.scenario, n_max)
                for c, n in zip(part, ns)]
        fields = {f.name: _stack_lanes(tuple(getattr(s, f.name)
                                             for s in scns), dtype=dtype)
                  for f in dataclasses.fields(Scenario)}
        batch = sharding.pad_batch_lanes(ScenarioBatch(
            scenarios=Scenario(**fields),
            mask=jnp.asarray(np.arange(n_max)[None, :] < ns[:, None]),
            n_classes=jnp.asarray(ns)), target)
        if mesh is not None:
            batch = jax.device_put(batch, sharding.lane_sharding(mesh))
    return batch, len(part)


def _solve_cold(candidates: Sequence[Candidate], cfg: SolverConfig,
                chunk: int, n_max: int):
    """Chunked cold solves through the engine's batched path.

    Every chunk is inert-lane padded to the same fixed width (one compiled
    program for the whole plan); results are trimmed back to real lanes.
    Bit-equal to one ``CapacityEngine.solve`` over all candidates because
    lanes are independent and the padding is solver-inert.

    Parameters
    ----------
    candidates : sequence of Candidate
        Design points, grid order.
    cfg : SolverConfig
        Solver knobs / kernel / mesh.
    chunk : int
        Real lanes per dispatch.
    n_max : int
        Shared padded class width of every chunk.

    Returns
    -------
    tuple
        ``(fields, n_chunks)`` with ``fields`` the per-candidate metric
        arrays dict.
    """
    engine = CapacityEngine(cfg, Policies(rounding=RoundingPolicy(False)))
    target = _chunk_targets(chunk, cfg)
    out = {k: [] for k in ("cost", "penalty", "total", "r", "iters",
                           "feasible")}
    n_chunks = 0
    for start in range(0, len(candidates), chunk):
        part = candidates[start:start + chunk]
        with span("plan.chunk", lanes=len(part)):
            batch, real = _stack_chunk(part, n_max, target,
                                       cfg.effective_dtype(), cfg.mesh)
            report = engine.solve(batch, check_feasible=False)
            sol = report.fractional
            with span("plan.pull"):
                out["cost"].append(np.asarray(sol.cost)[:real])
                out["penalty"].append(np.asarray(sol.penalty)[:real])
                out["total"].append(np.asarray(sol.total)[:real])
                out["r"].append(np.asarray(sol.r)[:real])
                out["iters"].append(np.asarray(report.iters)[:real])
                out["feasible"].append(np.asarray(report.feasible)[:real])
        n_chunks += 1
    return {k: np.concatenate(v) for k, v in out.items()}, n_chunks


def _solve_warm(spec: PlanSpec, candidates: Sequence[Candidate],
                cfg: SolverConfig, chunk: int, n_max: int):
    """Deadline-axis warm-started solves (opt-in ``solve_plan`` mode).

    The grid's deadline axis is innermost, so the candidates factor into
    ``cross = B / D`` deadline-sweep chains of length ``D``.  Chains are
    chunked into fixed lane sets; each chain solves its first deadline
    step cold, then seeds every later step's initial allocation from the
    previous step's equilibrium — with bids restarted at the cold
    ``rho_bar`` init, which preserves the exact Alg. 4.1 iterate
    trajectory (iterates are bid-driven; the init ``r`` enters only the
    first iteration's convergence metric, so results match the cold solve
    bit-for-bit whenever both runs stop at the same iteration, and stay
    within the stopping tolerance otherwise).

    Parameters
    ----------
    spec : PlanSpec
        Supplies the deadline-axis length (chain structure).
    candidates : sequence of Candidate
        The spec's full grid, grid order.
    cfg : SolverConfig
        Solver knobs / kernel / mesh.
    chunk : int
        Real lanes (chains) per dispatch.
    n_max : int
        Shared padded class width of every chunk.

    Returns
    -------
    tuple
        ``(fields, n_chunks)`` as in the cold path.
    """
    D = len(spec.deadline_scales)
    B = len(candidates)
    cross = B // D
    target = _chunk_targets(chunk, cfg)
    dt = cfg.effective_dtype()

    fields = {
        "cost": np.empty(B), "penalty": np.empty(B), "total": np.empty(B),
        "r": np.empty((B, n_max)), "iters": np.empty(B, dtype=int),
        "feasible": np.empty(B, dtype=bool),
    }
    n_chunks = 0
    for c0 in range(0, cross, chunk):
        chains = range(c0, min(c0 + chunk, cross))
        prev_r = None
        for d in range(D):
            part = [candidates[ci * D + d] for ci in chains]
            with span("plan.chunk", lanes=len(part)):
                batch, real = _stack_chunk(part, n_max, target, dt,
                                           cfg.mesh)
                init = game.cold_start(batch)
                if prev_r is not None:
                    init = init._replace(
                        r=jnp.where(batch.mask, prev_r, init.r))
                sol = game.solve_distributed_batch(
                    batch, eps_bar=cfg.eps_bar, lam=cfg.lam,
                    max_iters=cfg.max_iters, sweep_fn=cfg.sweep_fn,
                    init=init, mesh=cfg.mesh, iter_fn=cfg.iter_fn)
                prev_r = sol.r
                rows = [c.index for c in part]
                with span("plan.pull"):
                    fields["cost"][rows] = np.asarray(sol.cost)[:real]
                    fields["penalty"][rows] = np.asarray(sol.penalty)[:real]
                    fields["total"][rows] = np.asarray(sol.total)[:real]
                    fields["r"][rows] = np.asarray(sol.r)[:real]
                    fields["iters"][rows] = np.asarray(sol.iters)[:real]
                    fields["feasible"][rows] = np.asarray(
                        sol.feasible)[:real]
            n_chunks += 1
    return fields, n_chunks


def solve_plan(plan: Union[PlanSpec, Sequence[Candidate]], *,
               config: Optional[SolverConfig] = None, chunk: int = 64,
               warm_start: bool = False) -> PlanReport:
    """Solve every design point of a plan and reduce to a frontier report.

    Candidates are packed into fixed-width inert-lane-padded chunks and
    solved on the engine's batched Algorithm 4.1 path (one compiled
    program for the whole plan, lane-sharded over ``config.mesh`` when
    set).  Rounding is off — planning compares *fractional* equilibria,
    as the paper's what-if sweeps do — and infeasible candidates are
    reported via their ``feasible`` flag rather than raised (probing
    undersized clusters is the point of the sweep).

    Parameters
    ----------
    plan : PlanSpec or sequence of Candidate
        A spec (expanded via :func:`generate_grid` here) or an
        already-expanded candidate list.
    config : SolverConfig, optional
        Solver knobs / kernel / mesh (default: the paper's).
    chunk : int, optional
        Real candidates per solve dispatch (the padded lane width; rounded
        up to the mesh's lane multiple when sharded).  Results are
        independent of ``chunk`` bit-for-bit.
    warm_start : bool, optional
        Seed each deadline step's allocation from the previous step's
        equilibrium along the grid's innermost (deadline) axis.  Requires
        ``plan`` to be a :class:`PlanSpec` (the chain structure comes from
        its axes).  Iterate trajectories are preserved (bids restart
        cold), so per-candidate results are bit-equal to the cold solve
        whenever both stop at the same iteration and within the stopping
        tolerance otherwise.

    Returns
    -------
    PlanReport
        Per-candidate objective decomposition + feasibility, with Pareto
        and cheapest-feasible queries.

    Raises
    ------
    ValueError
        ``chunk < 1``, or ``warm_start=True`` with a plain candidate list.
    """
    cfg = config if config is not None else SolverConfig()
    if chunk < 1:
        raise ValueError(f"chunk={chunk} must be >= 1")
    if isinstance(plan, PlanSpec):
        spec: Optional[PlanSpec] = plan
        candidates = generate_grid(plan)
    else:
        spec = None
        candidates = list(plan)
    if warm_start and spec is None:
        raise ValueError(
            "warm_start=True needs a PlanSpec (the deadline-axis chain "
            "structure comes from its axes) — pass the spec, not the "
            "expanded candidate list")
    t0 = time.perf_counter()
    if not candidates:
        return _empty_report(cfg, chunk, warm_start)
    n_max = max(c.scenario.n for c in candidates)
    if warm_start:
        fields, n_chunks = _solve_warm(spec, candidates, cfg, chunk, n_max)
    else:
        fields, n_chunks = _solve_cold(candidates, cfg, chunk, n_max)
    return PlanReport(candidates=list(candidates), config=cfg, chunk=chunk,
                      n_chunks=n_chunks, warm_start=warm_start,
                      elapsed_s=time.perf_counter() - t0, **fields)
