"""Capacity planner (core/planning.py) + shared trace library (core/traces).

The load-bearing claim: ``solve_plan``'s chunked, inert-lane-padded sweep
is **bit-equal** to one direct ``CapacityEngine.solve`` over every
candidate — sharded and unsharded — because lanes are independent and the
padding is solver-inert.  Around it: grid determinism under the spec seed,
Pareto-frontier dominance invariants, the deadline-axis warm-start
contract (bit-equal when the stopping iteration matches, tolerance-bounded
otherwise), empty/all-infeasible spaces, and the workload-trace profile
properties (sorted, non-negative gaps, target mean rate) shared with the
admission daemon via bit-compatible re-exports."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.core import planning, sharding, traces
from repro.core.engine import (CapacityEngine, Policies, RoundingPolicy,
                               SolverConfig, _cast_floats)
from repro.core.planning import (PlanSpec, VMTier, generate_grid,
                                 solve_plan)
from repro.core.types import ScenarioBatch, stack_scenarios
from repro.serving import allocd

SPEC = PlanSpec(
    n_classes=3, profile="flash", rate=40.0, trace_events=128,
    cluster_sizes=(900.0, 4000.0),
    vm_tiers=(VMTier("small", 1.0, 6.0), VMTier("big", 2.0, 10.0)),
    deadline_scales=(0.9, 1.0, 1.15), penalty_scales=(1.0,), seed=3)

RESULT_FIELDS = ("cost", "penalty", "total", "r", "iters", "feasible")

#: The same design space at one class fewer: candidates the planner must
#: class-pad on the host when they share a chunk with ``SPEC``'s.
NARROW = dataclasses.replace(SPEC, n_classes=2)


@pytest.fixture(scope="module")
def grid():
    return generate_grid(SPEC)


@pytest.fixture(scope="module")
def report(grid):
    return solve_plan(grid, chunk=5)          # 12 candidates -> 5+5+2 ragged


def reference_solve(grid, cfg):
    """One direct CapacityEngine.solve over ALL candidates (the oracle the
    chunked planner must match bit-for-bit), trimmed to real lanes."""
    n_max = max(c.scenario.n for c in grid)
    batch = stack_scenarios([c.scenario for c in grid], n_max=n_max)
    if cfg.mesh is not None:
        batch = sharding.pad_batch_lanes(
            batch, sharding.padded_lane_count(len(grid),
                                              cfg.mesh.devices.size))
    engine = CapacityEngine(cfg, Policies(rounding=RoundingPolicy(False)))
    rep = engine.solve(batch, check_feasible=False)
    sol = rep.fractional
    B = len(grid)
    return {"cost": np.asarray(sol.cost)[:B],
            "penalty": np.asarray(sol.penalty)[:B],
            "total": np.asarray(sol.total)[:B],
            "r": np.asarray(sol.r)[:B],
            "iters": np.asarray(rep.iters)[:B],
            "feasible": np.asarray(rep.feasible)[:B]}


# --------------------------------------------------------------------------
# Grid generation
# --------------------------------------------------------------------------

def test_grid_deterministic_under_seed(grid):
    """Same spec -> bit-identical candidates; different seed -> different."""
    again = generate_grid(SPEC)
    assert len(again) == len(grid) == SPEC.n_candidates == 12
    for a, b in zip(grid, again):
        assert a.index == b.index and a.coords == b.coords
        for x, y in zip(jax.tree_util.tree_leaves(a.scenario),
                        jax.tree_util.tree_leaves(b.scenario)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    other = generate_grid(dataclasses.replace(SPEC, seed=SPEC.seed + 1))
    assert any(
        not np.array_equal(np.asarray(a.scenario.A), np.asarray(b.scenario.A))
        for a, b in zip(grid, other))


def test_grid_order_deadline_innermost(grid):
    """Candidate order: index == position, deadline axis innermost (what
    the warm-start chains rely on), coordinates round-trip the spec."""
    D = len(SPEC.deadline_scales)
    for pos, c in enumerate(grid):
        assert c.index == pos
        assert c.coords["deadline_scale"] == SPEC.deadline_scales[pos % D]
    # adjacent candidates within a chain differ ONLY in the deadline coord
    a, b = grid[0].coords, grid[1].coords
    assert a["deadline_scale"] != b["deadline_scale"]
    assert {k: v for k, v in a.items() if k != "deadline_scale"} \
        == {k: v for k, v in b.items() if k != "deadline_scale"}
    # tier slots scale capacity: same class draws, bigger cM under "big"
    small, big = grid[0].scenario, grid[D].scenario
    np.testing.assert_array_equal(np.asarray(small.A), np.asarray(big.A))
    np.testing.assert_array_equal(np.asarray(big.cM),
                                  2.0 * np.asarray(small.cM))


def test_grid_validation():
    with pytest.raises(ValueError, match="profile"):
        generate_grid(PlanSpec(profile="nope"))
    with pytest.raises(ValueError, match="n_classes"):
        generate_grid(PlanSpec(n_classes=0))
    with pytest.raises(ValueError, match="trace_events"):
        generate_grid(PlanSpec(trace_events=0))


# --------------------------------------------------------------------------
# Chunked solve == one-shot engine solve (the planner's core contract)
# --------------------------------------------------------------------------

def test_chunked_plan_bit_equal_one_shot(grid, report):
    ref = reference_solve(grid, SolverConfig())
    assert report.n_chunks == 3 and report.chunk == 5
    for k in RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(report, k), ref[k],
                                      err_msg=k)


def test_chunked_plan_bit_equal_one_shot_sharded(grid):
    mesh = sharding.lane_mesh()
    cfg = SolverConfig(mesh=mesh)
    ref = reference_solve(grid, cfg)
    rep = solve_plan(grid, config=cfg, chunk=5)
    for k in RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(rep, k), ref[k], err_msg=k)


def test_chunk_width_is_invisible(grid, report):
    """Any chunking of the same grid produces identical reports."""
    whole = solve_plan(grid, chunk=len(grid))
    assert whole.n_chunks == 1
    for k in RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(report, k), getattr(whole, k),
                                      err_msg=k)


def test_solve_plan_accepts_spec(grid, report):
    """Passing the PlanSpec itself expands the same grid internally."""
    rep = solve_plan(SPEC, chunk=5)
    for k in RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(rep, k), getattr(report, k),
                                      err_msg=k)


def test_solve_plan_rejects_bad_args(grid):
    with pytest.raises(ValueError, match="chunk"):
        solve_plan(grid, chunk=0)
    with pytest.raises(ValueError, match="warm"):
        solve_plan(grid, warm_start=True)     # plain list has no axes


# --------------------------------------------------------------------------
# Chunk stacking: compiled per field, bit-equal to the eager stack
# --------------------------------------------------------------------------

def eager_stack(part, n_max, target, dtype):
    """The eager composition the planner's stack program replaces:
    ``stack_scenarios`` -> ``pad_batch_lanes`` -> ``_cast_floats``."""
    batch = stack_scenarios([c.scenario for c in part], n_max=n_max)
    batch = sharding.pad_batch_lanes(batch, target)
    if dtype is None:
        return batch
    return ScenarioBatch(scenarios=_cast_floats(batch.scenarios, dtype),
                         mask=batch.mask, n_classes=batch.n_classes)


@pytest.mark.parametrize("case", ["uniform", "ragged", "inert", "cast",
                                  "mesh"])
def test_stack_chunk_bit_equal_eager(grid, case):
    """Every leaf, ``mask`` and ``n_classes`` equal the eager stack's bit
    for bit and in dtype, for uniform and ragged lanes, inert lanes, a
    float64 -> float32 cast and a lane-sharded 4-device mesh."""
    narrow = generate_grid(NARROW)
    part = list(grid[:5])
    target, dtype, mesh = 5, None, None
    if case in ("ragged", "mesh"):
        part = [grid[0], narrow[1], grid[2], narrow[3], grid[4]]
    if case == "inert":
        target = 8
    if case == "cast":
        dtype = jnp.float32
    if case == "mesh":
        mesh = sharding.lane_mesh(4)
        target = sharding.padded_lane_count(len(part), 4)
    got, real = planning._stack_chunk(part, 3, target, dtype, mesh)
    want = eager_stack(part, 3, target, dtype)
    assert real == len(part) and got.batch_size == target
    got_leaves = jax.tree_util.tree_leaves(got)
    want_leaves = jax.tree_util.tree_leaves(want)
    assert len(got_leaves) == len(want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if dtype is not None:
        assert got.scenarios.A.dtype == jnp.float32
        assert got.scenarios.A.dtype != grid[0].scenario.A.dtype
    if case in ("ragged", "mesh"):
        # a class-padded lane bids its own rho_bar in the padded slots
        rho_up = np.asarray(got.scenarios.rho_up)
        rho_bar = np.asarray(got.scenarios.rho_bar)
        for b in (1, 3):
            assert not np.asarray(got.mask)[b, 2]
            assert rho_up[b, 2] == rho_bar[b]
    if mesh is not None:
        sh = sharding.lane_sharding(mesh)
        assert all(leaf.sharding == sh for leaf in got_leaves)


def test_stack_program_compiles_once_per_width(grid):
    """Two chunks of equal width share the stack programs (one for the
    per-class fields, one for the scalars), and each answer is its own
    candidates' (nothing is kept across calls)."""
    planning._stack_lanes.clear_cache()
    first = solve_plan(grid[:6], chunk=6)
    assert planning._stack_lanes._cache_size() == 2
    second = solve_plan(grid[6:], chunk=6)
    whole = solve_plan(grid, chunk=6)
    assert planning._stack_lanes._cache_size() == 2
    for k in RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(first, k),
                                      getattr(whole, k)[:6], err_msg=k)
        np.testing.assert_array_equal(getattr(second, k),
                                      getattr(whole, k)[6:], err_msg=k)
    assert not np.array_equal(first.total, second.total)


@pytest.mark.parametrize("narrow_lanes", [0, 1, 3])
def test_stack_span_counts_ragged_lanes(grid, monkeypatch, narrow_lanes):
    """``repro.plan.stack`` carries ``ragged``: the lanes padded on the
    host, 0 for a uniform grid and k for k narrow candidates."""
    seen = []
    real_span = planning.span

    def spy(name, **meta):
        if name == "plan.stack":
            seen.append(meta["ragged"])
        return real_span(name, **meta)

    monkeypatch.setattr(planning, "span", spy)
    part = (generate_grid(NARROW)[:narrow_lanes]
            + list(grid[narrow_lanes:6]))
    rep = solve_plan(part, chunk=len(part))
    assert seen == [narrow_lanes] and rep.n_chunks == 1


# --------------------------------------------------------------------------
# Warm start along the deadline axis
# --------------------------------------------------------------------------

def test_warm_start_matches_cold(grid, report):
    """Warm-seeding preserves the bid-driven Alg. 4.1 trajectory: lanes
    that stop at the same iteration are bit-equal to the cold solve; a
    lane whose first-iteration convergence metric moved across eps_bar
    may stop at a different iteration, landing within the stopping
    tolerance of the same equilibrium."""
    warm = solve_plan(SPEC, chunk=4, warm_start=True)
    assert warm.warm_start and warm.n_candidates == report.n_candidates
    np.testing.assert_array_equal(warm.feasible, report.feasible)
    same = warm.iters == report.iters
    # the first deadline step of every chain is solved cold in both modes
    assert same[::len(SPEC.deadline_scales)].all()
    for k in ("cost", "penalty", "total", "r"):
        np.testing.assert_array_equal(
            np.asarray(getattr(warm, k))[same],
            np.asarray(getattr(report, k))[same], err_msg=k)
    scale = np.maximum(np.abs(report.r), 1.0)
    rel = np.max(np.abs(warm.r - report.r) / scale, axis=-1)
    assert np.all(rel[~same] <= 2 * SolverConfig().eps_bar)


# --------------------------------------------------------------------------
# Frontier queries
# --------------------------------------------------------------------------

def test_pareto_frontier_invariants(report):
    front = report.pareto_frontier()
    assert front.size >= 1
    assert report.feasible[front].all()
    assert np.all(np.diff(report.cost[front]) > 0)       # strictly up
    assert np.all(np.diff(report.penalty[front]) < 0)    # strictly down
    feas = np.flatnonzero(report.feasible)
    for i in front:                     # nothing feasible dominates a point
        assert not any(
            report.cost[j] <= report.cost[i]
            and report.penalty[j] <= report.penalty[i]
            and (report.cost[j] < report.cost[i]
                 or report.penalty[j] < report.penalty[i])
            for j in feas)
    for j in feas:                      # everything else is covered
        if j in front:
            continue
        assert any(report.cost[i] <= report.cost[j]
                   and report.penalty[i] <= report.penalty[j]
                   for i in front)


def test_cheapest_feasible_queries(report):
    i = report.cheapest_feasible()
    front = report.pareto_frontier()
    assert i == int(front[0])           # min cost, ties by penalty/index
    feas = np.flatnonzero(report.feasible)
    assert report.cost[i] == report.cost[feas].min()
    budget = float(np.median(report.penalty[feas]))
    j = report.cheapest_feasible(max_penalty=budget)
    qual = feas[report.penalty[feas] <= budget]
    assert j in qual and report.cost[j] == report.cost[qual].min()
    none = report.cheapest_feasible(
        max_penalty=float(report.penalty[feas].min()) - 1.0)
    assert none is None
    payload = report.to_json()
    assert payload["n_candidates"] == report.n_candidates
    assert payload["cheapest_feasible"]["index"] == i
    assert [p["index"] for p in payload["frontier"]] == [int(k) for k in
                                                         front]


def test_empty_design_space():
    empty = PlanSpec(cluster_sizes=())
    assert empty.n_candidates == 0 and generate_grid(empty) == []
    rep = solve_plan(empty)
    assert rep.n_candidates == 0 and rep.n_chunks == 0
    assert rep.pareto_frontier().size == 0
    assert rep.cheapest_feasible() is None
    assert solve_plan([], chunk=3).n_candidates == 0


def test_all_infeasible_space():
    """An undersized fleet is a legitimate probe result, not an error:
    every flag False, empty frontier, no cheapest design."""
    tiny = PlanSpec(n_classes=3, cluster_sizes=(2.0,),
                    vm_tiers=(VMTier("small", 1.0, 6.0),),
                    deadline_scales=(1.0,), seed=3)
    rep = solve_plan(tiny)
    assert rep.n_candidates == 1 and not rep.feasible.any()
    assert rep.pareto_frontier().size == 0
    assert rep.cheapest_feasible() is None
    assert rep.to_json()["cheapest_feasible"] is None


# --------------------------------------------------------------------------
# Shared workload-trace library (core/traces.py)
# --------------------------------------------------------------------------

def test_allocd_reexports_are_the_library():
    """serving.allocd re-exports core.traces bit-compatibly: the SAME
    function objects, so daemon traces and planner sizing share one
    implementation (and BENCH_allocd baselines keep their meaning)."""
    assert allocd.ARRIVAL_PROFILES is traces.ARRIVAL_PROFILES
    for name in ("poisson_times", "flash_crowd_times", "diurnal_times",
                 "bursty_times", "straggler_times"):
        assert getattr(allocd, name) is getattr(traces, name)
    assert set(traces.ARRIVAL_PROFILES) == {"poisson", "flash", "diurnal",
                                            "bursty", "straggler"}
    assert traces.ARRIVAL_PROFILES["bursty"] is traces.bursty_times


def test_trace_determinism_and_validation():
    a = traces.straggler_times(5, 64, 10.0)
    np.testing.assert_array_equal(a, traces.straggler_times(5, 64, 10.0))
    assert not np.array_equal(a, traces.straggler_times(6, 64, 10.0))
    with pytest.raises(ValueError, match="tail_index"):
        traces.straggler_times(0, 16, 10.0, tail_index=1.0)


# --------------------------------------------------------------------------
# Trace profile properties (hypothesis; loud skip when absent)
# --------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16),
       name=st.sampled_from(sorted(traces.ARRIVAL_PROFILES)),
       rate=st.floats(5.0, 200.0))
def test_prop_trace_profiles_well_formed(seed, name, rate):
    """Every profile yields n finite, sorted, non-negative-gap arrival
    times; the stationary profiles (poisson/bursty/straggler) hit the
    target mean rate (flash/diurnal take `rate` as the baseline/trough
    rate, so their realized mean is deliberately higher)."""
    n = 512
    t = traces.ARRIVAL_PROFILES[name](seed, n, rate)
    assert t.shape == (n,) and np.all(np.isfinite(t))
    assert t[0] >= 0.0 and np.all(np.diff(t) >= 0.0)
    realized = n / t[-1]
    if name in ("poisson", "bursty", "straggler"):
        assert 0.5 * rate < realized < 1.5 * rate
    else:
        assert realized > rate              # bursts only add arrivals


if not HAVE_HYPOTHESIS:
    pass  # @given shims the tests into loud skips (tests/_hypothesis_compat)
