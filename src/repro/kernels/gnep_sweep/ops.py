"""Jit'd wrapper + plug-in for repro.core.game.rm_solve(sweep_fn=...)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gnep_sweep.kernel import rm_sweep, rm_sweep_batched
from repro.kernels.gnep_sweep.ref import reference, reference_batched


def sweep(inc, spare, p_sorted, *, force_pallas=False):
    """Single-instance sweep for ``rm_solve(sweep_fn=...)``: the compiled
    kernel on TPU (never interpret mode there), interpret mode off it
    under ``force_pallas``, the jnp reference otherwise."""
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or force_pallas:
        return rm_sweep(inc.astype(jnp.float32), spare,
                        p_sorted.astype(jnp.float32),
                        interpret=not on_tpu)
    return reference(inc, spare, p_sorted)


@functools.lru_cache(maxsize=None)
def make_sweep_fn(force_pallas=False):
    # memoized: sweep_fn is a *static* jit argument compared by identity in
    # the game solvers, so returning the same object per config keeps
    # repeated solves on the compiled program instead of retracing.
    def fn(inc, spare, p_sorted):
        return sweep(inc, spare, p_sorted, force_pallas=force_pallas)
    # distinct per config: SolverConfig.fingerprint() records this name
    fn.__name__ = f"gnep_sweep(force_pallas={force_pallas})"
    return fn


def sweep_batched(inc, spare, p_sorted, *, force_pallas=False):
    """Batched sweep for ``solve_distributed_batch(sweep_fn=...)``:
    (B, Nc, N) x (B,) x (B, N) -> one kernel launch on TPU, jnp ref off it."""
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or force_pallas:
        return rm_sweep_batched(inc.astype(jnp.float32),
                                spare.astype(jnp.float32),
                                p_sorted.astype(jnp.float32),
                                interpret=not on_tpu)
    return reference_batched(inc, spare, p_sorted)


@functools.lru_cache(maxsize=None)
def make_batched_sweep_fn(force_pallas=False):
    # memoized for the same jit-cache reason as make_sweep_fn: every
    # `solve_batch(..., sweep_fn=make_batched_sweep_fn())` epoch must reuse
    # one function object or the whole batched solver recompiles.
    def fn(inc, spare, p_sorted):
        return sweep_batched(inc, spare, p_sorted, force_pallas=force_pallas)
    # distinct per config: SolverConfig.fingerprint() records this name
    fn.__name__ = f"gnep_sweep_batched(force_pallas={force_pallas})"
    return fn
