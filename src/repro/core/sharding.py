"""Device-sharded scenario fleets: lane-parallel GNEP solves via shard_map.

The batched engine (``game.solve_distributed_batch``) already solves B
independent lanes as one XLA program; this module places those lanes on a
1-D :class:`jax.sharding.Mesh` so the fleet splits across devices — the
distributed-by-construction structure of the paper (independent Class
Managers per game, independent games per lane) maps directly onto hardware:

* :func:`lane_mesh` builds the 1-D mesh over the ``"lanes"`` axis;
* :func:`pad_batch_lanes` pads the lane count to a multiple of the device
  count with *inert* lanes — the lane-axis analog of the per-class padding
  convention (``types.neutral_class_values``): an inert lane has an
  all-False mask, unit capacity/cost scalars and converges in one
  iteration, so it never changes any real lane's trajectory.  The same
  construction backs dynamic windows: ``AdmissionWindow.add_lane`` builds
  its new row with it, and because :func:`solve_sharded_batch` re-derives
  the padding from the *current* lane count on every call, windows that
  grow, shrink or compact between solves stay valid on a resident mesh
  (the repad is mesh-aware by construction);
* :func:`solve_sharded_batch` runs Algorithm 4.1 under
  ``jax.shard_map``: each device iterates a local
  ``while_loop`` over its own lane slice, with the per-lane convergence
  freezing and :class:`~repro.core.game.BatchWarmStart` warm starts of the
  unsharded solver fully preserved.

Because every update in the batched solver is lane-local (the only
cross-lane coupling is the *global* loop condition, and converged lanes
are frozen by masking), each device's local loop reproduces its lanes'
unsharded trajectories exactly — and exits as soon as *its own* lanes
converge instead of spinning until the globally slowest lane does.  The
sharded result therefore matches the unsharded solver to float precision
(``tests/test_sharding.py`` asserts <= 1e-6; in practice bit-equal) while
scaling lane throughput with the device count
(``benchmarks/allocator_perf.py --shard``).

Works anywhere: on CPU, force a multi-device topology with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (what
``tests/conftest.py`` and ``scripts/ci.sh`` do).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import game
from repro.core.types import (Scenario, ScenarioBatch, Solution, WindowState,
                              neutral_class_values)

#: Default name of the single mesh axis the lane dimension is sharded over.
LANE_AXIS = "lanes"


def lane_mesh(n_devices: Optional[int] = None, *, devices=None,
              axis_name: str = LANE_AXIS) -> Mesh:
    """Build the 1-D device mesh the lane axis shards over.

    Parameters
    ----------
    n_devices : int, optional
        How many devices to use; defaults to every addressable device.
        Must not exceed the available count.
    devices : sequence of jax.Device, optional
        Explicit device list (overrides ``n_devices``); defaults to a
        prefix of ``jax.devices()``.
    axis_name : str, optional
        Mesh axis name (default :data:`LANE_AXIS`).

    Returns
    -------
    jax.sharding.Mesh
        A 1-D mesh suitable for every ``mesh=`` parameter in this repo's
        solver stack (``solve_distributed_batch``, ``solve_batch``,
        ``solve_streaming``, ``epoch_batch``, ``epoch_stream``).
    """
    if devices is None:
        avail = jax.devices()
        n = len(avail) if n_devices is None else int(n_devices)
        if not 1 <= n <= len(avail):
            raise ValueError(
                f"n_devices={n} out of range [1, {len(avail)}] "
                "(on CPU, force more with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        devices = avail[:n]
    return Mesh(np.asarray(devices), (axis_name,))


def lane_sharding(mesh: Mesh) -> NamedSharding:
    """The sharding every lane-axis leaf uses: first dim split over ``mesh``.

    Parameters
    ----------
    mesh : jax.sharding.Mesh
        1-D mesh from :func:`lane_mesh`.

    Returns
    -------
    jax.sharding.NamedSharding
        ``PartitionSpec(axis)`` over the mesh's single axis — valid for
        every leaf of a :class:`ScenarioBatch` / ``BatchWarmStart`` /
        ``Solution`` (all carry the lane dim first).
    """
    (axis,) = mesh.axis_names
    return NamedSharding(mesh, PartitionSpec(axis))


def shard_batch(batch: ScenarioBatch, mesh: Mesh) -> ScenarioBatch:
    """Pad ``batch`` to the mesh's lane multiple and place it on the mesh.

    :func:`solve_sharded_batch` does this internally per call; for
    steady-state throughput (fleet sweeps re-solving a resident batch) do
    it ONCE and pass the result — subsequent solves then start with zero
    host->device resharding, which is where the sharded engine's
    near-linear lane throughput comes from
    (``benchmarks/allocator_perf.py --shard``).

    Parameters
    ----------
    batch : ScenarioBatch
        The real B lanes (any placement).
    mesh : jax.sharding.Mesh
        1-D lane mesh the batch will be solved on.

    Returns
    -------
    ScenarioBatch
        Inert-lane padded to a multiple of the device count, every leaf
        device_put with :func:`lane_sharding`.  Note the padding is part
        of the batch from here on: solves of the resident batch return the
        padded lane count (trim with the mask / ``n_classes``, or index
        the original B lanes).
    """
    padded = pad_batch_lanes(
        batch, padded_lane_count(batch.batch_size, mesh.devices.size))
    sh = lane_sharding(mesh)
    return jax.tree_util.tree_map(lambda leaf: jax.device_put(leaf, sh),
                                  padded)


def padded_lane_count(batch_size: int, n_shards: int) -> int:
    """Smallest multiple of ``n_shards`` that is >= ``batch_size``.

    Parameters
    ----------
    batch_size : int
        Real lane count B.
    n_shards : int
        Device count of the lane mesh.

    Returns
    -------
    int
        The lane count after inert-lane padding (shard_map needs the
        sharded axis divisible by the mesh size).
    """
    if batch_size < 1 or n_shards < 1:
        raise ValueError("batch_size and n_shards must be >= 1")
    return -(-batch_size // n_shards) * n_shards


def pad_batch_lanes(batch: ScenarioBatch, target_b: int) -> ScenarioBatch:
    """Append inert lanes so ``batch`` has exactly ``target_b`` lanes.

    The lane-axis analog of the per-class padding convention: an inert lane
    holds a full row of neutral classes (:func:`~repro.core.types
    .neutral_class_values`), an all-False mask row, and unit scalars
    (``R = rho_bar = rho_hat = 1``) so every solver formula stays finite,
    the lane is trivially feasible, and its convergence metric is 0 — it
    freezes after at most one iteration and exchanges nothing with real
    lanes (lanes are independent by construction).

    Parameters
    ----------
    batch : ScenarioBatch
        The real B lanes.
    target_b : int
        Lane count after padding; must be >= ``batch.batch_size``.

    Returns
    -------
    ScenarioBatch
        ``batch`` itself when ``target_b == batch.batch_size``, else a new
        batch with ``target_b - B`` inert lanes appended.
    """
    b = batch.batch_size
    if target_b == b:
        return batch
    if target_b < b:
        raise ValueError(f"target_b={target_b} < batch_size={b}")
    pad, n_max = target_b - b, batch.n_max
    dt = batch.scenarios.A.dtype
    neutral = neutral_class_values(1.0)
    kw = {}
    for f in dataclasses.fields(Scenario):
        leaf = getattr(batch.scenarios, f.name)
        if f.name in neutral:                           # per-class (B, n_max)
            fill = jnp.full((pad, n_max), neutral[f.name], dt)
        else:                                           # scalar (B,)
            fill = jnp.ones((pad,), dt)
        kw[f.name] = jnp.concatenate([leaf, fill], axis=0)
    return ScenarioBatch(
        scenarios=Scenario(**kw),
        mask=jnp.concatenate(
            [batch.mask, jnp.zeros((pad, n_max), bool)], axis=0),
        n_classes=jnp.concatenate(
            [batch.n_classes,
             jnp.zeros((pad,), batch.n_classes.dtype)], axis=0))


def pad_warm_start(init: game.BatchWarmStart,
                   target_b: int) -> game.BatchWarmStart:
    """Append *frozen* inert-lane state so ``init`` covers ``target_b`` lanes.

    Padded lanes get ``active = False`` (the while-loop never touches them:
    zero iterations, zero work), a zero allocation, and bids/price pinned to
    the inert lane's ``rho_bar = 1`` — consistent with
    :func:`pad_batch_lanes` so the pass-through state is self-consistent.

    Parameters
    ----------
    init : game.BatchWarmStart
        Warm start over the real B lanes.
    target_b : int
        Lane count after padding; must be >= B.

    Returns
    -------
    game.BatchWarmStart
        ``init`` itself when already ``target_b`` lanes, else the padded
        warm start.
    """
    b = init.active.shape[0]
    if target_b == b:
        return init
    if target_b < b:
        raise ValueError(f"target_b={target_b} < batch_size={b}")
    pad, n_max = target_b - b, init.r.shape[1]
    dt = init.r.dtype
    return game.BatchWarmStart(
        r=jnp.concatenate([init.r, jnp.zeros((pad, n_max), dt)], axis=0),
        bids=jnp.concatenate([init.bids, jnp.ones((pad, n_max), dt)], axis=0),
        rho=jnp.concatenate([init.rho, jnp.ones((pad,), dt)], axis=0),
        lane_iters=jnp.concatenate(
            [init.lane_iters, jnp.zeros((pad,), init.lane_iters.dtype)],
            axis=0),
        active=jnp.concatenate(
            [init.active, jnp.zeros((pad,), bool)], axis=0))


def pad_window_state(state: WindowState, target_b: int) -> WindowState:
    """Append *inert-lane* equilibrium rows so ``state`` covers ``target_b``
    lanes.

    The stored-state analog of :func:`pad_warm_start`: padded lanes get a
    zero allocation, price pinned at the inert lane's ``rho_bar = 1``, zero
    iteration counts and ``solved = True`` — so a resident warm start built
    from the padded state freezes them (``active = False``) exactly like
    :func:`pad_warm_start` does, and they never iterate.

    Parameters
    ----------
    state : WindowState
        Committed equilibrium over the real B lanes.
    target_b : int
        Lane count after padding; must be >= B.

    Returns
    -------
    WindowState
        ``state`` itself when already ``target_b`` lanes, else the padded
        state.
    """
    b = state.solved.shape[0]
    if target_b == b:
        return state
    if target_b < b:
        raise ValueError(f"target_b={target_b} < batch_size={b}")
    pad, n_max = target_b - b, state.r.shape[1]
    dt = state.r.dtype
    return WindowState(
        r=jnp.concatenate([state.r, jnp.zeros((pad, n_max), dt)], axis=0),
        rho=jnp.concatenate([state.rho, jnp.ones((pad,), dt)], axis=0),
        lane_iters=jnp.concatenate(
            [state.lane_iters, jnp.zeros((pad,), state.lane_iters.dtype)],
            axis=0),
        solved=jnp.concatenate([state.solved, jnp.ones((pad,), bool)],
                               axis=0))


@jax.jit
def _resident_warm_builder(batch: ScenarioBatch, r, rho, lane_iters, solved,
                           dirty) -> game.BatchWarmStart:
    # Same frozen/dirty split as AdmissionWindow.warm_start, computed
    # on-device over the PADDED resident leaves (sharding propagates, so the
    # init comes out lane-sharded with zero host round-trips).  Every output
    # leaf passes through an optimization_barrier: the donated-init contract
    # of solve_resident_batch requires leaves that are fresh buffers, and
    # the barrier breaks any jaxpr-level passthrough (e.g. same-dtype
    # ``astype`` in cold_start returning its operand) that would otherwise
    # alias an init leaf to live window state.
    cold = game.cold_start(batch)
    frozen = solved & jnp.logical_not(dirty)
    init = game.BatchWarmStart(
        r=jnp.where(frozen[:, None], r, cold.r),
        bids=cold.bids,
        rho=jnp.where(frozen, rho, cold.rho),
        lane_iters=jnp.where(frozen, lane_iters, jnp.zeros_like(lane_iters)),
        active=jnp.logical_not(frozen))
    return jax.tree_util.tree_map(jax.lax.optimization_barrier, init)


@jax.jit
def _resident_cold_builder(batch: ScenarioBatch) -> game.BatchWarmStart:
    # Barrier for the same donation-safety reason as _resident_warm_builder:
    # cold_start's rho/bids are same-dtype casts of batch.rho_bar and would
    # otherwise pass the batch leaf straight through to the donated init.
    return jax.tree_util.tree_map(jax.lax.optimization_barrier,
                                  game.cold_start(batch))


def resident_warm_init(batch: ScenarioBatch, state: WindowState,
                       dirty) -> game.BatchWarmStart:
    """Build the donation-safe warm start for a mesh-resident window solve.

    Frozen lanes (``state.solved`` and not ``dirty``) pass their stored
    equilibrium through with ``active = False``; dirty or never-solved lanes
    restart from the cold Algorithm 4.1 init — bit-identical to
    ``AdmissionWindow.warm_start`` + :func:`pad_warm_start`, but computed in
    one jitted program over the already-padded resident leaves, so nothing
    round-trips through the host.  Every leaf of the result is a *fresh*
    buffer (an ``optimization_barrier`` guards against jaxpr passthrough
    aliasing), which is what lets :func:`solve_resident_batch` donate it.

    Parameters
    ----------
    batch : ScenarioBatch
        The resident (lane-padded, mesh-placed) batch.
    state : WindowState
        Committed equilibrium over the same padded lane count
        (:func:`pad_window_state`).
    dirty : jnp.ndarray
        (padded B,) bool — lanes whose scenario changed since ``state``
        (padding rows False).

    Returns
    -------
    game.BatchWarmStart
        Lane-sharded init whose buffers are safe to donate.
    """
    return _resident_warm_builder(batch, state.r, state.rho,
                                  state.lane_iters, state.solved, dirty)


def resident_cold_init(batch: ScenarioBatch) -> game.BatchWarmStart:
    """Donation-safe cold Algorithm 4.1 init for a mesh-resident batch.

    Value-identical to ``game.cold_start`` (so a resident first solve
    reproduces the round-trip cold trajectory exactly), with fresh buffers
    per the same barrier argument as :func:`resident_warm_init`.

    Parameters
    ----------
    batch : ScenarioBatch
        The resident (lane-padded, mesh-placed) batch.

    Returns
    -------
    game.BatchWarmStart
        Lane-sharded cold init whose buffers are safe to donate.
    """
    return _resident_cold_builder(batch)


@lru_cache(maxsize=None)
def _resident_solver(mesh: Mesh, eps_bar: float, lam: float, max_iters: int,
                     sweep_fn, iter_fn):
    """Memoized donating variant of :func:`_sharded_solver`.

    Identical program to the ``with_init=True`` sharded solver, but the
    warm-start argument's buffers are DONATED (``donate_argnums``) — XLA
    reuses them for the solution outputs, so steady-state resident
    streaming allocates no fresh equilibrium buffers per flush (the
    ``serving/engine.py`` decode-cache idiom applied to the GNEP loop).
    """
    (axis,) = mesh.axis_names
    spec = PartitionSpec(axis)

    def local_solve(batch: ScenarioBatch, init: game.BatchWarmStart):
        return game._solve_batch_core(batch, eps_bar, lam, max_iters,
                                      sweep_fn, init, iter_fn=iter_fn)

    sharded = jax.shard_map(local_solve, mesh=mesh, in_specs=(spec, spec),
                            out_specs=spec, check_vma=False)
    return jax.jit(sharded, donate_argnums=(1,))


def solve_resident_batch(batch: ScenarioBatch, mesh: Mesh, *,
                         eps_bar: float = 0.03, lam: float = 0.05,
                         max_iters: int = 200, sweep_fn=None,
                         init: game.BatchWarmStart, iter_fn=None) -> Solution:
    """Algorithm 4.1 over an already mesh-resident, lane-padded batch.

    The zero-copy flush path of device-resident window sessions: ``batch``
    must already be lane-padded to the mesh multiple and placed with
    :func:`lane_sharding` (a resident ``AdmissionWindow`` maintains exactly
    that), and ``init`` must come from :func:`resident_warm_init` /
    :func:`resident_cold_init` — its buffers are **donated** to the solve
    and must not be read afterwards.  Unlike :func:`solve_sharded_batch`
    nothing is padded, placed or trimmed here: the returned
    :class:`Solution` keeps the PADDED lane count and stays resident on the
    mesh, ready to be committed as the next warm-start state.

    Parameters
    ----------
    batch : ScenarioBatch
        Mesh-resident padded batch (lane count divisible by the device
        count).
    mesh : jax.sharding.Mesh
        1-D lane mesh the batch lives on.
    eps_bar : float, optional
        Alg. 4.1 stopping tolerance (compiled into the program).
    lam : float, optional
        Bid-escalation step (compiled in).
    max_iters : int, optional
        Per-device iteration cap (compiled in).
    sweep_fn : callable, optional
        Batched RM sweep override; pass a memoized function object.
    init : game.BatchWarmStart
        Fresh-buffer warm start over the padded lanes; donated.
    iter_fn : object, optional
        Fused-iteration override (see ``game.solve_distributed_batch``);
        inside ``shard_map`` its prep/step see the *local* lane slice.
        Pass a memoized object (it keys the program cache).

    Returns
    -------
    Solution
        Padded-lane-count solution, resident on ``mesh``.
    """
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"lane sharding needs a 1-D mesh, got axes {mesh.axis_names}")
    if batch.batch_size % mesh.devices.size:
        raise ValueError(
            f"resident batch has {batch.batch_size} lanes, not a multiple "
            f"of the {mesh.devices.size}-device mesh — pad with "
            "pad_batch_lanes/padded_lane_count first")
    solver = _resident_solver(mesh, float(eps_bar), float(lam),
                              int(max_iters), sweep_fn, iter_fn)
    return solver(batch, init)


@lru_cache(maxsize=None)
def _sharded_solver(mesh: Mesh, eps_bar: float, lam: float, max_iters: int,
                    sweep_fn, iter_fn, with_init: bool):
    """Memoized jitted shard_map'd Algorithm 4.1 for one solver config.

    Cached on (mesh, knobs, sweep_fn identity) so repeated solves — the
    streaming engine's steady state — reuse one compiled program exactly
    like the unsharded jit cache does.  ``with_init`` False compiles the
    cold start INTO the program (cold solves of a mesh-resident batch then
    run with zero per-call host-side work).
    """
    (axis,) = mesh.axis_names
    spec = PartitionSpec(axis)

    def local_solve(batch: ScenarioBatch, *init: game.BatchWarmStart):
        # Each device runs the plain batched solver over its own lane
        # slice: lane updates are lane-local and converged lanes freeze,
        # so local trajectories == unsharded trajectories, but the local
        # while_loop exits when the *local* lanes converge.
        return game._solve_batch_core(batch, eps_bar, lam, max_iters,
                                      sweep_fn, init[0] if init else None,
                                      iter_fn=iter_fn)

    sharded = jax.shard_map(local_solve, mesh=mesh,
                            in_specs=(spec, spec) if with_init else (spec,),
                            out_specs=spec, check_vma=False)
    return jax.jit(sharded)


def solve_sharded_batch(batch: ScenarioBatch, mesh: Mesh, *,
                        eps_bar: float = 0.03, lam: float = 0.05,
                        max_iters: int = 200, sweep_fn=None,
                        init: Optional[game.BatchWarmStart] = None,
                        iter_fn=None) -> Solution:
    """Algorithm 4.1 over B lanes sharded across the devices of ``mesh``.

    Semantics are identical to ``game.solve_distributed_batch`` (same
    per-lane trajectories, per-lane convergence freezing, warm starts); the
    lane axis is padded with inert lanes up to a multiple of the device
    count, each device solves its slice under ``shard_map``, and the
    padding is trimmed off the result.  Matches the unsharded solver to
    <= 1e-6 (in practice bit-equal) on every lane.

    Parameters
    ----------
    batch : ScenarioBatch
        B stacked (padded + masked) instances; B need *not* divide the
        device count — inert-lane padding handles ragged fleets.
    mesh : jax.sharding.Mesh
        1-D mesh from :func:`lane_mesh` (exactly one axis).
    eps_bar : float, optional
        Alg. 4.1 stopping tolerance (paper uses 0.03).  Unlike the
        unsharded path this is compiled into the program (one recompile
        per distinct value) — solver knobs, not data.
    lam : float, optional
        Bid-escalation step of ``cm_bid_update`` (same compile note).
    max_iters : int, optional
        Per-device iteration cap.
    sweep_fn : callable, optional
        Batched RM sweep override (e.g. the Pallas kernel); inside
        ``shard_map`` it sees the *local* ``(B/D, Nc, N)`` shapes.  Pass a
        memoized function object (it keys the program cache).
    init : game.BatchWarmStart, optional
        Warm start over the real B lanes (the streaming engine's frozen /
        dirty split); padded lanes are added frozen.  ``None`` = cold
        start.
    iter_fn : object, optional
        Fused-iteration override (see ``game.solve_distributed_batch``);
        inside ``shard_map`` its prep/step see the *local* lane slice.
        Pass a memoized object (it keys the program cache).

    Returns
    -------
    Solution
        Same layout as ``solve_distributed_batch``: leaves carry the REAL
        leading B dim (inert-lane padding already trimmed).
    """
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"lane sharding needs a 1-D mesh, got axes {mesh.axis_names}")
    b = batch.batch_size
    n_shards = mesh.devices.size
    target = padded_lane_count(b, n_shards)
    solver = _sharded_solver(mesh, float(eps_bar), float(lam),
                             int(max_iters), sweep_fn, iter_fn,
                             init is not None)
    # device_put is a no-op for leaves already placed by shard_batch, so the
    # steady state (resident sharded batch, e.g. fleet sweeps) pays zero
    # per-call resharding; a one-shot unsharded batch is placed here.  The
    # cold init is compiled into the program rather than materialized here.
    sh = lane_sharding(mesh)
    args = (jax.device_put(pad_batch_lanes(batch, target), sh),)
    if init is not None:
        args += (jax.device_put(pad_warm_start(init, target), sh),)
    sol = solver(*args)
    if target == b:
        return sol
    return jax.tree_util.tree_map(lambda leaf: leaf[:b], sol)
