"""Distributed game-theoretic formulation (paper Sec. 4).

Players: one Resource Manager (RM, problem P5) and N Class Managers (CMs,
problem P4).  Algorithm 4.1 iterates best replies until the relative
allocation change drops below ``eps_bar``.

Exact sub-solvers (DESIGN.md Sec. 3):

* **CM (P4)** — closed form, Prop. 4.1:  s^M = xi^M r, s^R = xi^R r,
  psi = clip(K / r, psi_low, psi_up).

* **RM (P5)** — mixed-integer in (r, y, rho), but for a *fixed* price rho the
  binary y_i = 1{rho_i^a >= rho} is forced by the big-M constraints and the
  remaining LP in r has all-positive objective coefficients
  ((rho - rho_bar) + p_i), so the optimum is the greedy knapsack: give every
  class its guaranteed r^low, then fill the slack R - sum(r^low) in
  p_i-descending order up to each class's price-dependent upper bound.
  The optimal price lies in the bid set {rho_i^a} (raising rho strictly
  increases revenue until it crosses a bid), so an exact sweep over the <= N+2
  candidate prices solves P5 to optimality.  The sweep is one (N_cand x N)
  masked prefix-sum — fully vectorized here and tiled in Pallas in
  ``repro.kernels.gnep_sweep``.

Both a jitted whole-game solver (`solve_distributed`) and a paper-faithful
serial loop (`solve_distributed_python`, one solve per CM per iteration — the
Fig. 7 baseline) are provided.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import Scenario, ScenarioBatch, Solution

# --------------------------------------------------------------------------
# Resource Manager — problem (P5)
# --------------------------------------------------------------------------
#
# The exact sweep is split into candidates -> fill -> pick so the batched
# solver can route the O(Nc x N) fill of ALL instances through one Pallas
# kernel launch while the cheap prep/pick stages stay vmapped jnp.


def _rm_candidates(scn: Scenario, bids: jnp.ndarray, mask):
    """Candidate prices + greedy-order increments for the (P5) sweep.

    ``mask`` flags valid classes; padded classes bid rho_bar (a candidate that
    is always present anyway) and expose zero increment, so they are inert.
    """
    bids_eff = jnp.where(mask, bids, scn.rho_bar)
    p_eff = jnp.where(mask, scn.p, 0.0)
    # Candidate prices: all bids + the interval ends [rho_bar, rho_hat] (P5e).
    cand = jnp.concatenate([bids_eff, jnp.stack([scn.rho_bar, scn.rho_hat])])
    # y_i = 1 when CM i bids at least the price (free at equality; choosing 1
    # can only enlarge the feasible box, hence is optimal).
    y = (bids_eff[None, :] >= cand[:, None]) & mask[None, :]    # (Nc, N)

    # Greedy fill order: p descending (fixed across candidates).  Valid
    # classes keep their relative order (argsort is stable, padded p = 0).
    order = jnp.argsort(-p_eff)
    inc_max = jnp.where(mask, scn.r_up - scn.r_low, 0.0)[order]  # (N,)
    inc = jnp.where(y[:, order], inc_max[None, :], 0.0)          # (Nc, N)
    spare = scn.R - jnp.sum(jnp.where(mask, scn.r_low, 0.0))
    return cand, inc, spare, p_eff[order], order


def _rm_pick(scn: Scenario, cand, fill, sum_fill, p_fill, order, mask):
    """Choose the best candidate row and undo the greedy permutation."""
    p_eff = jnp.where(mask, scn.p, 0.0)
    r_low = jnp.where(mask, scn.r_low, 0.0)
    sum_r = jnp.sum(r_low) + sum_fill
    p_r = jnp.sum(p_eff * r_low) + p_fill
    obj = (cand - scn.rho_bar) * sum_r + p_r \
        - jnp.sum(p_eff * jnp.where(mask, scn.r_up, 0.0))

    best = jnp.argmax(obj)
    rho = cand[best]
    inv = jnp.argsort(order)
    r = r_low + (fill[best])[inv]
    return rho, r, obj[best]


def rm_solve(scn: Scenario, bids: jnp.ndarray, *, mask=None, sweep_fn=None):
    """Exact solution of the Resource Manager's problem (P5) given CM bids.

    Parameters
    ----------
    scn : Scenario
        The instance (uses r_low/r_up/p/R/rho_bar/rho_hat).
    bids : jnp.ndarray
        (N,) current CM bids rho_i^a, each in [rho_bar, rho_up_i] [cents].
    mask : jnp.ndarray, optional
        (N,) validity mask — padded classes (mask False) never receive
        capacity and never contribute a candidate price.
    sweep_fn : callable, optional
        Override of the candidate-sweep inner loop,
        ``sweep_fn(inc (Nc, N), spare (), p_sorted (N,)) -> (fill, sum_fill,
        p_fill)`` — the Pallas kernel plugs in here.

    Returns
    -------
    rho : jnp.ndarray
        Optimal unit price (a bid or an interval end of (P5e)) [cents].
    r : jnp.ndarray
        (N,) optimal allocation: guaranteed ``r_low`` plus the greedy
        p-descending fill of the slack up to each admitted class's ``r_up``.
    objective : jnp.ndarray
        The (P5) objective at (rho, r).
    """
    if mask is None:
        mask = jnp.ones(bids.shape, bool)
    cand, inc, spare, p_sorted, order = _rm_candidates(scn, bids, mask)

    if sweep_fn is None:
        cum = jnp.cumsum(inc, axis=1)
        fill = jnp.clip(spare - (cum - inc), 0.0, inc)          # (Nc, N)
        sum_fill = jnp.sum(fill, axis=1)
        # full f32 on the TPU (its default matmul precision rounds the
        # operands to bf16); a no-op on the CPU
        p_fill = jnp.matmul(fill, p_sorted,
                            precision=jax.lax.Precision.HIGHEST)
    else:
        fill, sum_fill, p_fill = sweep_fn(inc, spare, p_sorted)

    return _rm_pick(scn, cand, fill, sum_fill, p_fill, order, mask)


# --------------------------------------------------------------------------
# Class Managers — problem (P4), Prop. 4.1 closed form
# --------------------------------------------------------------------------


def cm_best_response(scn: Scenario, r: jnp.ndarray, *, mask=None):
    """Closed-form optimum of each CM's (P4) given its allocation (Prop 4.1).

    Parameters
    ----------
    scn : Scenario
        The instance (uses xiM/xiR/K and the psi box).
    r : jnp.ndarray
        (N,) chips granted by the RM to each class.
    mask : jnp.ndarray, optional
        (N,) validity mask; padded classes (r = 0) get psi = psi_low (never
        "rejecting") and zero slots instead of the 0-division garbage.

    Returns
    -------
    psi : jnp.ndarray
        (N,) inverse admitted concurrency, clipped to the SLA box
        [psi_low, psi_up] = [1/H_up, 1/H_low].
    sM, sR : jnp.ndarray
        (N,) map / reduce slots, the Prop. 4.1 split ``s = xi * r``.
    """
    if mask is None:
        sM = scn.xiM * r
        sR = scn.xiR * r
        psi = jnp.clip(scn.K / r, scn.psi_low, scn.psi_up)
        return psi, sM, sR
    r_safe = jnp.where(r > 0, r, 1.0)
    psi = jnp.clip(scn.K / r_safe, scn.psi_low, scn.psi_up)
    psi = jnp.where(mask, psi, scn.psi_low)
    sM = jnp.where(mask, scn.xiM * r, 0.0)
    sR = jnp.where(mask, scn.xiR * r, 0.0)
    return psi, sM, sR


def cm_bid_update(scn: Scenario, bids, rho, psi, lam: float, *, mask=None):
    """Alg. 4.1 lines 11-13: the bid escalation (pseudo-gradient) step.

    A CM still rejecting jobs (psi > psi_low) raises its bid by a fixed
    fraction of its budget, ``lam * rho_up``, from ``max(bid, rho)``,
    clipped to the (P4b) box [rho_bar, rho_up]; satisfied CMs keep theirs.

    Parameters
    ----------
    scn : Scenario
        The instance (uses psi_low, rho_up).
    bids : jnp.ndarray
        (N,) current bids rho_i^a [cents].
    rho : jnp.ndarray
        Scalar price the RM just posted.
    psi : jnp.ndarray
        (N,) each CM's best-response inverse concurrency.
    lam : float
        Escalation step (paper uses 0.05); larger converges faster but
        overshoots the equilibrium price further.
    mask : jnp.ndarray, optional
        (N,) validity mask; padded classes never escalate.

    Returns
    -------
    jnp.ndarray
        (N,) updated bids.
    """
    rejecting = psi > scn.psi_low * (1.0 + 1e-9)
    if mask is not None:
        rejecting = rejecting & mask
    raised = jnp.minimum(jnp.maximum(bids, rho) + lam * scn.rho_up, scn.rho_up)
    return jnp.where(rejecting, raised, bids)


# --------------------------------------------------------------------------
# Algorithm 4.1 — best reply (jitted, whole game as one XLA program)
# --------------------------------------------------------------------------


class GameState(NamedTuple):
    r: jnp.ndarray
    bids: jnp.ndarray
    rho: jnp.ndarray
    eps: jnp.ndarray
    it: jnp.ndarray


@partial(jax.jit, static_argnames=("max_iters",))
def solve_distributed(scn: Scenario, *, eps_bar: float = 0.03,
                      lam: float = 0.05, max_iters: int = 200) -> Solution:
    """Algorithm 4.1 (RM/CM best-reply) for one instance, as one XLA program.

    Parameters
    ----------
    scn : Scenario
        One allocation instance over N job classes.
    eps_bar : float, optional
        Stopping tolerance on the relative allocation change
        ``sum_i |r_i' - r_i| / r_i`` (paper uses 0.03).
    lam : float, optional
        Bid-escalation step of :func:`cm_bid_update`.
    max_iters : int, optional
        Iteration cap (static jit argument).

    Returns
    -------
    Solution
        The GNEP equilibrium: ``aux`` carries the final RM price rho,
        ``iters`` the best-reply iterations run.  ``feasible`` flags
        ``sum(r_low) <= R`` and all E_i < 0; the trajectory is still
        well-defined when False, but the equilibrium is meaningless.
    """
    feasible = (jnp.sum(scn.r_low) <= scn.R) & jnp.all(scn.E < 0)
    dt = scn.A.dtype

    def cond(s: GameState):
        return (s.eps >= eps_bar) & (s.it < max_iters)

    def body(s: GameState):
        rho, r_new, _ = rm_solve(scn, s.bids)
        psi, _, _ = cm_best_response(scn, r_new)
        bids = cm_bid_update(scn, s.bids, rho, psi, lam)
        eps = jnp.sum(jnp.abs(r_new - s.r) / s.r)
        return GameState(r_new, bids, rho, eps, s.it + 1)

    init = GameState(r=scn.r_low, bids=jnp.full_like(scn.r_low, scn.rho_bar),
                     rho=scn.rho_bar.astype(dt),
                     eps=jnp.asarray(jnp.inf, dt), it=jnp.asarray(0))
    final = jax.lax.while_loop(cond, body, init)

    psi, sM, sR = cm_best_response(scn, final.r)
    cost = scn.rho_bar * jnp.sum(final.r)
    penalty = jnp.sum(scn.alpha * psi - scn.beta)
    return Solution(r=final.r, psi=psi, sM=sM, sR=sR, cost=cost,
                    penalty=penalty, total=cost + penalty, feasible=feasible,
                    iters=final.it, aux=final.rho)


# --------------------------------------------------------------------------
# Batched Algorithm 4.1 — B scenarios as ONE vmapped while_loop XLA program
# --------------------------------------------------------------------------


class BatchGameState(NamedTuple):
    r: jnp.ndarray          # (B, n_max)
    bids: jnp.ndarray       # (B, n_max)
    rho: jnp.ndarray        # (B,)
    active: jnp.ndarray     # (B,) bool — lane still iterating
    lane_iters: jnp.ndarray  # (B,) per-instance iteration count
    it: jnp.ndarray         # global loop counter


class BatchWarmStart(NamedTuple):
    """Per-lane initial state for a warm-started ``solve_distributed_batch``.

    Lanes with ``active`` False are *frozen*: the while-loop never updates
    them, so their ``r`` / ``rho`` / ``lane_iters`` pass straight through to
    the returned :class:`Solution` — this is how the streaming engine carries
    an already-converged lane's equilibrium across re-solves for free.  Lanes
    with ``active`` True iterate Algorithm 4.1 from (``r``, ``bids``) exactly
    as the cold solver would from its own init.

    Attributes
    ----------
    r : jnp.ndarray
        (B, n_max) initial allocation (stored equilibrium for frozen lanes,
        masked ``r_low`` for lanes restarting cold).
    bids : jnp.ndarray
        (B, n_max) initial CM bids.  NOTE: to reproduce the cold Alg. 4.1
        trajectory (and hence its equilibrium) a re-iterating lane must start
        from the paper's init ``bids = rho_bar`` — bids only escalate during
        the game, so carrying converged bids over changes the equilibrium.
    rho : jnp.ndarray
        (B,) initial RM price (pass-through value for frozen lanes).
    lane_iters : jnp.ndarray
        (B,) int32 starting iteration counters (stored count for frozen
        lanes so ``Solution.iters`` stays meaningful, 0 for cold restarts).
    active : jnp.ndarray
        (B,) bool — True for lanes that should iterate.
    """
    r: jnp.ndarray
    bids: jnp.ndarray
    rho: jnp.ndarray
    lane_iters: jnp.ndarray
    active: jnp.ndarray


def cold_start(batch: ScenarioBatch) -> BatchWarmStart:
    """The cold Algorithm 4.1 init for every lane of ``batch``.

    Parameters
    ----------
    batch : ScenarioBatch
        Stacked instances; padded classes get r = 0 and a neutral bid.

    Returns
    -------
    BatchWarmStart
        ``r = r_low`` (masked), ``bids = rho_bar``, ``rho = rho_bar``,
        zero iteration counters, every lane active.  Passing this to
        ``solve_distributed_batch(init=...)`` is identical to ``init=None``.
    """
    scns, mask = batch.scenarios, batch.mask
    dt = scns.A.dtype
    r0 = jnp.where(mask, scns.r_low, 0.0)
    return BatchWarmStart(
        r=r0,
        bids=jnp.broadcast_to(scns.rho_bar[:, None], r0.shape).astype(dt),
        rho=scns.rho_bar.astype(dt),
        lane_iters=jnp.zeros((batch.batch_size,), jnp.int32),
        active=jnp.ones((batch.batch_size,), bool))


def _lane_eps(r_new, r_old, mask):
    """Alg. 4.1 convergence metric, restricted to valid classes."""
    rel = jnp.abs(r_new - r_old) / jnp.where(r_old > 0, r_old, 1.0)
    return jnp.sum(jnp.where(mask, rel, 0.0))


def _solve_batch_core(batch: ScenarioBatch, eps_bar, lam, max_iters,
                      sweep_fn, init: Optional[BatchWarmStart],
                      iter_fn=None) -> Solution:
    """Traceable body of the batched Algorithm 4.1 (see the public wrapper
    ``solve_distributed_batch`` for semantics).  Called directly — on the
    local lane slice — by the shard_map body in ``repro.core.sharding``."""
    scns, mask = batch.scenarios, batch.mask
    dt = scns.A.dtype

    feasible = jax.vmap(
        lambda s, m: (jnp.sum(jnp.where(m, s.r_low, 0.0)) <= s.R)
        & jnp.all(jnp.where(m, s.E < 0, True)))(scns, mask)

    if sweep_fn is None:
        def rm_batch(bids):
            return jax.vmap(lambda s, b, m: rm_solve(s, b, mask=m)
                            )(scns, bids, mask)
    else:
        # prep/pick stay vmapped; the O(B x Nc x N) fill is one batched call.
        def rm_batch(bids):
            cand, inc, spare, p_sorted, order = jax.vmap(_rm_candidates)(
                scns, bids, mask)
            fill, sum_fill, p_fill = sweep_fn(inc, spare, p_sorted)
            return jax.vmap(_rm_pick)(scns, cand, fill.astype(dt),
                                      sum_fill.astype(dt), p_fill.astype(dt),
                                      order, mask)

    if iter_fn is not None:
        # fused path: the iteration-invariant prep (greedy order, slack,
        # r_low aggregates) is hoisted out of the while_loop once; each
        # body evaluation is one fused step (repro.kernels.gnep_iter).
        prep = iter_fn.prepare(scns, mask)

        def iterate(s: BatchGameState):
            return iter_fn.step(prep, scns, mask, s.r, s.bids, lam)
    else:
        def iterate(s: BatchGameState):
            rho, r_new, _ = rm_batch(s.bids)
            psi, _, _ = jax.vmap(
                lambda scn, r, m: cm_best_response(scn, r, mask=m)
            )(scns, r_new, mask)
            bids_new = jax.vmap(
                lambda scn, b, rh, ps, m: cm_bid_update(scn, b, rh, ps, lam,
                                                        mask=m)
            )(scns, s.bids, rho, psi, mask)
            eps = jax.vmap(_lane_eps)(r_new, s.r, mask)
            return r_new, rho, bids_new, eps

    def cond(s: BatchGameState):
        return jnp.any(s.active) & (s.it < max_iters)

    def body(s: BatchGameState):
        r_new, rho, bids_new, eps = iterate(s)

        act = s.active
        keep = act[:, None]
        return BatchGameState(
            r=jnp.where(keep, r_new, s.r),
            bids=jnp.where(keep, bids_new, s.bids),
            rho=jnp.where(act, rho, s.rho),
            active=act & (eps >= eps_bar),
            lane_iters=s.lane_iters + act.astype(s.lane_iters.dtype),
            it=s.it + 1)

    if init is None:
        init = cold_start(batch)
    state0 = BatchGameState(
        r=init.r, bids=init.bids, rho=init.rho, active=init.active,
        lane_iters=init.lane_iters.astype(jnp.int32), it=jnp.asarray(0))
    final = jax.lax.while_loop(cond, body, state0)

    psi, sM, sR = jax.vmap(lambda scn, r, m: cm_best_response(scn, r, mask=m)
                           )(scns, final.r, mask)
    cost = scns.rho_bar * jnp.sum(final.r, axis=1)
    pen = jnp.sum(jnp.where(mask, scns.alpha * psi - scns.beta, 0.0), axis=1)
    return Solution(r=final.r, psi=psi, sM=sM, sR=sR, cost=cost,
                    penalty=pen, total=cost + pen, feasible=feasible,
                    iters=final.lane_iters, aux=final.rho)


@partial(jax.jit, static_argnames=("max_iters", "sweep_fn", "iter_fn"))
def _solve_batch_jit(batch: ScenarioBatch, *, eps_bar, lam, max_iters,
                     sweep_fn, init: Optional[BatchWarmStart],
                     iter_fn=None) -> Solution:
    """The single-program (unsharded) jit of ``_solve_batch_core``."""
    return _solve_batch_core(batch, eps_bar, lam, max_iters, sweep_fn, init,
                             iter_fn=iter_fn)


def solve_distributed_batch(batch: ScenarioBatch, *, eps_bar: float = 0.03,
                            lam: float = 0.05, max_iters: int = 200,
                            sweep_fn=None,
                            init: Optional[BatchWarmStart] = None,
                            mesh=None, iter_fn=None) -> Solution:
    """Algorithm 4.1 for B stacked scenarios as a single XLA program.

    One ``while_loop`` drives all lanes; converged lanes are frozen by
    masking (their state stops updating, their iteration counter stops) so
    every lane reproduces its single-instance ``solve_distributed`` trajectory
    bit-for-bit while the loop keeps running for the stragglers.  The loop
    exits when every lane has converged (per-instance early exit).

    Parameters
    ----------
    batch : ScenarioBatch
        B stacked (padded + masked) instances; see ``stack_scenarios``.
    eps_bar : float, optional
        Alg. 4.1 stopping tolerance on the per-lane relative allocation
        change ``sum_i |r_i' - r_i| / r_i`` (paper uses 0.03).
    lam : float, optional
        Bid-escalation (pseudo-gradient) step: a rejecting CM raises its bid
        by ``lam * rho_up`` per iteration (Alg. 4.1 line 12).
    max_iters : int, optional
        Global iteration cap (static: changing it recompiles).
    sweep_fn : callable, optional
        *Batched* RM sweep override taking ``(inc (B, Nc, N), spare (B,),
        p_sorted (B, N))`` — the batched Pallas kernel
        (``repro.kernels.gnep_sweep.ops.make_batched_sweep_fn``) plugs in
        here so the price sweep of all B scenarios is one kernel launch.
        Static jit argument: pass a memoized function object.
    init : BatchWarmStart, optional
        Warm start for the streaming engine: lanes with ``init.active``
        False are frozen at their stored equilibrium (zero iterations),
        active lanes iterate from ``init.r`` / ``init.bids``.  ``None``
        (default) is the cold Alg. 4.1 init for every lane (``cold_start``).
        This is the plumbing the event-coalesced epochs ride: however many
        events an ``EventEpoch`` folds, the flush arrives here as one init
        whose ``active`` set is the union of the dirtied lanes — and after
        an ``AdmissionWindow.compact()`` the window hands in the *remapped*
        stored equilibrium, so frozen lanes pass through bit-identically on
        the packed layout.
    mesh : jax.sharding.Mesh, optional
        1-D device mesh (see ``repro.core.sharding.lane_mesh``): lanes are
        padded to a multiple of the device count with inert lanes and each
        device iterates its own slice under ``shard_map`` — per-lane
        results match the unsharded path to <= 1e-6 (in practice
        bit-equal).  ``None`` (default) keeps the whole batch on one
        device.
    iter_fn : object, optional
        Fused-iteration override (``repro.kernels.gnep_iter.ops
        .make_fused_iter_fn``): an object with ``prepare(scns, mask)``
        and ``step(prep, scns, mask, r, bids, lam)`` whose prep is
        hoisted out of the while_loop and whose step runs one full
        Alg. 4.1 inner iteration (sweep + pick + psi + bid update + eps)
        as one fused region / kernel launch.  Mutually exclusive with
        ``sweep_fn`` in spirit — when both are given, ``iter_fn`` wins
        (the fused step subsumes the sweep).  Static jit argument: pass
        a memoized object.  ``None`` (default) keeps the unfused chain.

    Returns
    -------
    Solution
        Leaves carry a leading batch dim: r/psi/sM/sR are (B, n_max) with
        padded classes identically zero; cost, penalty, total, feasible,
        iters and aux (= final RM price rho) are (B,).
    """
    if mesh is not None:
        from repro.core.sharding import solve_sharded_batch
        return solve_sharded_batch(batch, mesh, eps_bar=eps_bar, lam=lam,
                                   max_iters=max_iters, sweep_fn=sweep_fn,
                                   init=init, iter_fn=iter_fn)
    return _solve_batch_jit(batch, eps_bar=eps_bar, lam=lam,
                            max_iters=max_iters, sweep_fn=sweep_fn, init=init,
                            iter_fn=iter_fn)


# --------------------------------------------------------------------------
# Paper-faithful serial implementation (Fig. 7 baseline)
# --------------------------------------------------------------------------


def _rm_solve_np(scn, bids):
    """Numpy RM solve (single price sweep), used by the serial baseline."""
    p = np.asarray(scn.p)
    r_low, r_up = np.asarray(scn.r_low), np.asarray(scn.r_up)
    R = float(scn.R)
    rho_bar = float(scn.rho_bar)
    cand = np.concatenate([bids, [rho_bar, float(scn.rho_hat)]])
    order = np.argsort(-p)
    spare = R - r_low.sum()
    best_obj, best_rho, best_r = -np.inf, rho_bar, r_low.copy()
    const = (p * r_up).sum()
    for c in cand:
        y = bids >= c
        inc = np.where(y[order], (r_up - r_low)[order], 0.0)
        cum = np.cumsum(inc)
        fill = np.clip(spare - (cum - inc), 0.0, inc)
        r_sorted = r_low[order] + fill
        obj = (c - rho_bar) * r_sorted.sum() + (p[order] * r_sorted).sum() - const
        if obj > best_obj:
            best_obj, best_rho = obj, c
            best_r = np.empty_like(r_sorted)
            best_r[order] = r_sorted
    return best_rho, best_r


def solve_distributed_python(scn: Scenario, *, eps_bar: float = 0.03,
                             lam: float = 0.05, max_iters: int = 200,
                             per_cm_callback: Optional[Callable] = None):
    """Algorithm 4.1 exactly as written: a Python ``repeat`` loop, the RM
    solve, then one (P4) solve *per CM* in a Python for-loop.

    This mirrors the paper's serial testbed (Sec. 5.3) whose per-CM timings
    are divided by N to estimate distributed wall-clock; used as the Fig. 7
    / §Perf baseline.

    Parameters
    ----------
    scn : Scenario
        One allocation instance.
    eps_bar, lam, max_iters
        As in :func:`solve_distributed`.
    per_cm_callback : callable, optional
        ``f(i, r_i, sM_i, sR_i, psi_i)`` invoked after each CM's (P4) solve
        (instrumentation hook for the timing experiments).

    Returns
    -------
    sol : Solution
        The equilibrium (same layout as :func:`solve_distributed`).
    n_iters : int
        Best-reply iterations run.
    cm_seconds : list of float
        Wall-clock seconds of the serial CM loop, one entry per iteration.
    """
    import time

    n = scn.n
    A = np.asarray(scn.A); B = np.asarray(scn.B); E = np.asarray(scn.E)
    cMv = np.asarray(scn.cM); cRv = np.asarray(scn.cR)
    K = np.asarray(scn.K); xiM = np.asarray(scn.xiM); xiR = np.asarray(scn.xiR)
    psi_low = np.asarray(scn.psi_low); psi_up = np.asarray(scn.psi_up)
    rho_up = np.asarray(scn.rho_up)
    rho_bar = float(scn.rho_bar)

    r = np.asarray(scn.r_low).copy()
    bids = np.full(n, rho_bar)
    psi = psi_up.copy()
    cm_seconds = []
    it = 0
    rho = rho_bar
    while it < max_iters:
        r_old = r.copy()
        rho, r = _rm_solve_np(scn, bids)
        t0 = time.perf_counter()
        for i in range(n):  # executed in parallel by real CMs (paper Sec. 4.4)
            # Prop. 4.1 closed form, one scalar class at a time
            sMi = xiM[i] * r[i]
            sRi = xiR[i] * r[i]
            psi_i = min(max(K[i] / r[i], psi_low[i]), psi_up[i])
            psi[i] = psi_i
            if psi_i > psi_low[i] * (1 + 1e-9):
                bids[i] = min(max(bids[i], rho) + lam * rho_up[i], rho_up[i])
            if per_cm_callback is not None:
                per_cm_callback(i, r[i], sMi, sRi, psi_i)
        cm_seconds.append(time.perf_counter() - t0)
        it += 1
        eps = float(np.sum(np.abs(r - r_old) / r_old))
        if eps < eps_bar:
            break

    sM = xiM * r
    sR = xiR * r
    cost = rho_bar * r.sum()
    penalty = float((np.asarray(scn.alpha) * psi - np.asarray(scn.beta)).sum())
    sol = Solution(
        r=jnp.asarray(r), psi=jnp.asarray(psi), sM=jnp.asarray(sM),
        sR=jnp.asarray(sR), cost=jnp.asarray(cost), penalty=jnp.asarray(penalty),
        total=jnp.asarray(cost + penalty),
        feasible=jnp.asarray(bool((np.asarray(scn.r_low).sum() <= float(scn.R))
                                  and np.all(E < 0))),
        iters=jnp.asarray(it), aux=jnp.asarray(rho))
    return sol, it, cm_seconds


def distributed_walltime_estimate(n_cms: int, iters: int,
                                  serial_cm_seconds: float,
                                  rm_seconds: float = 0.0,
                                  net_rtt_s: float = 1.3e-4) -> float:
    """Paper Sec. 5.3 timing model for true-distributed wall-clock.

    Parameters
    ----------
    n_cms : int
        Number of Class Managers (the CM solves run in parallel).
    iters : int
        Best-reply iterations of the run being estimated.
    serial_cm_seconds : float
        Total serial CM-loop seconds measured by
        :func:`solve_distributed_python`.
    rm_seconds : float, optional
        RM solve seconds (not divided — the RM is a single player).
    net_rtt_s : float, optional
        Per-iteration network round-trip (two floats each way; default from
        a 100 Mb/s LAN micro-benchmark, ~130 us).

    Returns
    -------
    float
        Estimated distributed wall-clock seconds:
        ``serial_cm_seconds / N + rm_seconds + iters * net_rtt_s``.
    """
    return serial_cm_seconds / max(n_cms, 1) + rm_seconds + iters * net_rtt_s
