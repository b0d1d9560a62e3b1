"""The paper's hot spot, tiled: the RM (P5) candidate-price sweep.

At N classes the exact RM solve is an O(N^2) masked running-sum: for each of
~N candidate prices, a greedy knapsack fill in fixed p-order.  This kernel
tiles it (BN classes x BC candidates per step); the running per-candidate
cumulative fill is VMEM scratch carried across the sequential class axis,
and inside a tile the classes advance one at a time — the same column
recurrence as ``repro.kernels.gnep_iter`` — so each step is a handful of
VPU ops on a ``(1, BC)`` candidate row, with one pass over HBM.

``rm_sweep_batched`` runs the grid (B, Nc/BC, N/BN), so the price sweep of
a whole ScenarioBatch is ONE kernel launch: batch and candidate axes are
parallel, the class axis stays sequential per (batch, candidate-tile).
``rm_sweep`` is the same launch over a batch of one.

Layout (what Mosaic accepts on the TPU): the increments enter class-major,
``(N, Nc)``, so a class is a row of candidates loaded from an aligned group
of 8 rows; the penalty rates and the slack are SMEM scalars.  Everything
runs in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gnep_iter.kernel import I32_ZERO, ROW_GROUP, tiling


def _kernel(inc_ref, spare_ref, p_ref, fill_ref, sumf_ref, pf_ref,
            cum_scr, sacc_scr, pacc_scr, *, n_blocks, block_c, block_n,
            group):
    ji = pl.program_id(2)

    @pl.when(ji == 0)
    def _init():
        cum_scr[...] = jnp.zeros_like(cum_scr)
        sacc_scr[...] = jnp.zeros_like(sacc_scr)
        pacc_scr[...] = jnp.zeros_like(pacc_scr)

    spare = spare_ref[0, 0, 0]                        # this lane's slack
    rows = jax.lax.broadcasted_iota(jnp.int32, (group, block_c), 0)

    # cumsum along the class axis, as a recurrence: class j's fill is what
    # is left of the slack after every earlier class's increment, clipped
    # to its own increment.
    def _classes(g, carry):
        cum, sacc, pacc = carry
        base = pl.multiple_of(g * jnp.int32(group), group)
        block = inc_ref[0, pl.ds(base, group), :]     # (group, BC)
        tile = jnp.zeros_like(block)
        for k in range(group):
            inc = block[k:k + 1, :]                   # (1, BC)
            cum = cum + inc
            fill = jnp.clip(spare - (cum - inc), 0.0, inc)
            tile = jnp.where(rows == k, fill, tile)
            sacc = sacc + fill
            pacc = pacc + fill * p_ref[0, 0, base + k]
        fill_ref[0, pl.ds(base, group), :] = tile
        return cum, sacc, pacc

    cum, sacc, pacc = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(block_n // group), _classes,
        (cum_scr[...], sacc_scr[...], pacc_scr[...]))
    cum_scr[...] = cum
    sacc_scr[...] = sacc
    pacc_scr[...] = pacc

    @pl.when(ji == n_blocks - 1)
    def _final():
        sumf_ref[0] = sacc_scr[...]
        pf_ref[0] = pacc_scr[...]


@functools.partial(jax.jit, static_argnames=("block_c", "block_n",
                                             "interpret"))
def rm_sweep_batched(inc, spare, p_sorted, *, block_c=512, block_n=512,
                     interpret=False):
    """Batched RM price sweep: B instances in one kernel launch.

    inc: (B, Nc, N); spare: (B,); p_sorted: (B, N).  Computes in f32.
    Returns (fill (B, Nc, N) in ``inc``'s dtype, sum_fill (B, Nc) f32,
    p_fill (B, Nc) f32)."""
    B, Nc, N = inc.shape
    f32 = jnp.float32
    block_c, block_n, Ncp, Np = tiling(Nc, N, block_c, block_n, interpret)
    n_blocks = Np // block_n
    group = ROW_GROUP if block_n % ROW_GROUP == 0 else 1
    # class-major, padded to tile multiples (padding has inc = 0: no effect)
    inc_cm = jnp.pad(jnp.swapaxes(inc.astype(f32), 1, 2),
                     ((0, 0), (0, Np - N), (0, Ncp - Nc)))   # (B, Np, Ncp)
    p_p = jnp.pad(p_sorted.astype(f32), ((0, 0), (0, Np - N)))[:, None, :]
    spare_arr = jnp.asarray(spare, f32).reshape(B, 1, 1)

    smem = pltpu.SMEM
    fill, sumf, pf = pl.pallas_call(
        functools.partial(_kernel, n_blocks=n_blocks, block_c=block_c,
                          block_n=block_n, group=group),
        grid=(B, Ncp // block_c, n_blocks),
        in_specs=[
            pl.BlockSpec((1, block_n, block_c),
                         lambda bi, ci, ji: (bi, ji, ci)),
            pl.BlockSpec((1, 1, 1),
                         lambda bi, ci, ji: (bi, I32_ZERO, I32_ZERO),
                         memory_space=smem),
            pl.BlockSpec((1, 1, block_n),
                         lambda bi, ci, ji: (bi, I32_ZERO, ji),
                         memory_space=smem),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n, block_c),
                         lambda bi, ci, ji: (bi, ji, ci)),
            pl.BlockSpec((1, 1, block_c),
                         lambda bi, ci, ji: (bi, I32_ZERO, ci)),
            pl.BlockSpec((1, 1, block_c),
                         lambda bi, ci, ji: (bi, I32_ZERO, ci)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Np, Ncp), f32),
            jax.ShapeDtypeStruct((B, 1, Ncp), f32),
            jax.ShapeDtypeStruct((B, 1, Ncp), f32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_c), f32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(inc_cm, spare_arr, p_p)
    fill = jnp.swapaxes(fill[:, :N, :Nc], 1, 2).astype(inc.dtype)
    return fill, sumf[:, 0, :Nc], pf[:, 0, :Nc]


def rm_sweep(inc, spare, p_sorted, *, block_c=512, block_n=512,
             interpret=False):
    """inc: (Nc, N); spare: scalar; p_sorted: (N,).  One instance of
    :func:`rm_sweep_batched`.
    Returns (fill (Nc, N), sum_fill (Nc,), p_fill (Nc,))."""
    fill, sumf, pf = rm_sweep_batched(
        inc[None], jnp.reshape(jnp.asarray(spare), (1,)), p_sorted[None],
        block_c=block_c, block_n=block_n, interpret=interpret)
    return fill[0], sumf[0], pf[0]
