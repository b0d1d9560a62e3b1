"""Jit'd wrapper + plug-in for ``SolverConfig.iter_fn`` / game ``iter_fn=``.

``make_fused_iter_fn()`` returns the memoized :class:`FusedIterFn` object
the batched solvers accept as their ``iter_fn`` plug point: ``prepare``
hoists the iteration-invariant tensors out of the while_loop and ``step``
runs one fused Alg. 4.1 inner iteration.  Off-TPU the fused middle is the
pure-jnp formulation of ``ref.py`` (already one fused XLA region — the
win over the unfused chain is the hoisted prep and, under
``dtype_policy="f32_checked"``, the halved element width); on TPU the
O(B x Nc x N) middle is the single compiled Pallas launch of
``kernel.py`` — never interpret mode there — and off-TPU
``force_pallas=True`` (which the tests use) runs the same kernel in
interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gnep_iter import ref
from repro.kernels.gnep_iter.kernel import fused_iter_call


def _middle_pallas(prep: ref.IterPrep, cand, bids_sorted, *, interpret):
    """Pallas middle for ``ref.iter_step``: one launch, then the best-row
    pick.  Compiled for the TPU it computes in f32 (Mosaic has no f64
    vector path); interpret mode keeps the input dtype so the f64
    differential tests stay exact.  The best-row pick is a one-hot
    contraction over the kernel's class-major fill, honoring
    ``iter_step``'s no-gather invariant (the contraction has one nonzero
    per row, so it moves the kernel's bits unchanged)."""
    dt = bids_sorted.dtype

    def cast(x):
        return x if interpret else x.astype(jnp.float32)

    fill_cm, _, best, rho = fused_iter_call(
        cast(bids_sorted), cast(prep.inc_max_sorted), cast(prep.p_sorted),
        cast(cand), cast(prep.spare), cast(prep.rho_bar),
        cast(prep.sum_r_low), cast(prep.p_r_low), cast(prep.const),
        interpret=interpret)
    best_onehot = best[:, None] == jnp.arange(fill_cm.shape[2])
    fill_best = jnp.sum(jnp.where(best_onehot[:, None, :], fill_cm, 0.0),
                        axis=2)
    return fill_best.astype(dt), best, rho.astype(dt)


class FusedIterFn:
    """The ``iter_fn`` plug-point object of the batched Alg. 4.1 solvers.

    Hashable by identity and carrying a stable ``__name__`` — it is a
    *static* jit argument in ``game._solve_batch_jit`` and a cache key in
    the sharded solvers, and ``SolverConfig.fingerprint()`` records the
    name.  Always obtain instances via :func:`make_fused_iter_fn` (which
    memoizes per config) so repeated solves reuse one compiled program.

    Parameters
    ----------
    name : str
        Stable identifier recorded in the config fingerprint.
    middle_fn : callable or None
        Override of the O(B x Nc x N) middle passed through to
        ``ref.iter_step`` (None = pure-jnp reference middle).
    """

    def __init__(self, name: str, middle_fn=None):
        self.__name__ = name
        self._middle_fn = middle_fn

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"<FusedIterFn {self.__name__}>"

    def prepare(self, scns, mask) -> ref.IterPrep:
        """Hoist the iteration-invariant prep (see ``ref.prepare``).

        Parameters
        ----------
        scns : Scenario
            Stacked scenario leaves of the batch being solved.
        mask : jnp.ndarray
            (B, n_max) class-validity mask.

        Returns
        -------
        IterPrep
            Invariants to close over the while_loop body.
        """
        return ref.prepare(scns, mask)

    def step(self, prep, scns, mask, r, bids, lam):
        """One fused Alg. 4.1 inner iteration (see ``ref.iter_step``).

        Parameters
        ----------
        prep : IterPrep
            Invariants from :meth:`prepare`.
        scns : Scenario
            Stacked scenario leaves of the batch being solved.
        mask : jnp.ndarray
            (B, n_max) class-validity mask.
        r : jnp.ndarray
            (B, n_max) current allocation.
        bids : jnp.ndarray
            (B, n_max) current CM bids.
        lam : float
            Bid-escalation step.

        Returns
        -------
        tuple
            ``(r_new, rho, bids_new, eps)`` as in ``ref.iter_step``.
        """
        return ref.iter_step(prep, scns, mask, r, bids, lam,
                             middle_fn=self._middle_fn)


@functools.lru_cache(maxsize=None)
def make_fused_iter_fn(force_pallas: bool = False) -> FusedIterFn:
    """Build (and memoize) the fused-iteration plug-in for the solvers.

    Memoized for the same jit-cache reason as
    ``gnep_sweep.ops.make_batched_sweep_fn``: ``iter_fn`` is a static jit
    argument compared by identity, so every solve must see the same
    object per config or the whole batched solver retraces.

    Parameters
    ----------
    force_pallas : bool, optional
        Route the middle through the Pallas kernel even off-TPU (runs in
        interpret mode; the differential kernel tests use this).  The
        default picks Pallas on TPU and the fused jnp middle elsewhere.

    Returns
    -------
    FusedIterFn
        The plug-point object for ``SolverConfig(iter_fn=...)`` /
        ``solve_distributed_batch(iter_fn=...)``.
    """
    if jax.default_backend() == "tpu":
        middle = functools.partial(_middle_pallas, interpret=False)
    elif force_pallas:
        middle = functools.partial(_middle_pallas, interpret=True)
    else:
        middle = None
    return FusedIterFn(f"gnep_iter(force_pallas={force_pallas})", middle)
