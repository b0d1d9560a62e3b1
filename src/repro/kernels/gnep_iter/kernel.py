"""Fused Alg. 4.1 iteration middle, tiled: build -> fill -> objective -> pick.

``gnep_sweep`` tiles only the greedy fill of an *already materialized*
``inc`` tensor; every iteration of the batched solver still pays a chain
of jnp dispatches around it (admission pattern, objective, argmax,
gathers).  This kernel fuses the whole O(B x Nc x N) middle of one
Alg. 4.1 inner iteration into ONE launch over grid ``(B, Nc/BC, N/BN)``:

* the candidate admission pattern ``y = bids >= cand`` and the increment
  tensor ``inc = y * inc_max`` are built *inside* the kernel from the
  (B, N) bid vector — the (B, Nc, N) tensor never round-trips through HBM;
* the greedy running-sum fill is a column recurrence: the class axis is
  sequential and carries per-candidate ``cum`` / ``sum_fill`` /
  ``p_fill`` accumulators across class tiles in VMEM scratch, and
  *within* a tile the columns advance one at a time (a fori_loop seeded
  from the scratch carries) — exactly the recurrence of
  ``ref._scan_accumulators``, so every accumulator sees the same
  additions in the same order at ANY ``(block_c, block_n)`` tiling;
* at the last class tile the (P5) objective of the candidate tile is
  formed from the accumulators and folded into a running argmax scratch
  (best objective / index / price) carried across the *candidate* axis,
  so the winning candidate leaves the kernel as two scalars per lane.

Layout (what Mosaic accepts on the TPU).  The per-class column scalars
(bid, fill headroom, penalty rate) and the per-lane scalars live in SMEM,
where a column index that changes every loop step is a plain scalar
load.  Candidates run along lanes as ``(1, BC)`` rows; the fill tile is
written *class-major* ``(BN, BC)``, one aligned group of 8 class rows per
store.  Every block's last two dims are multiples of (8, 128) or the
array's own; a unit axis makes each per-lane row a ``(1, X)`` block.

A strictly-greater comparison across candidate tiles reproduces
``jnp.argmax``'s first-maximum semantics exactly; padded candidate
columns replicate the last real candidate (the (P5e) interval end
``rho_hat``) so a padded duplicate can never *strictly* beat the real
column it copies, and padded class columns expose ``inc_max = 0`` so they
are inert in the fill.  All arithmetic runs in the input dtype: in
interpret mode (off-TPU) the f64 kernel is bit-equal to
``repro.kernels.gnep_iter.ref`` at any tiling; the TPU path is f32 (see
``ops.py``).

The psi / bid-update / eps epilogue of the iteration stays jnp (it is
O(B x N) and fuses into the surrounding while-loop body for free); see
``ref.iter_step`` for the exact seam.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Class rows stored per aligned VMEM write (the f32 sublane tile).
ROW_GROUP = 8

#: Constant block index.  Mosaic takes i32 indices only; a bare ``0``
#: would become an i64 literal when ``jax_enable_x64`` is on.
I32_ZERO = np.int32(0)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tiling(n_cand: int, n_cls: int, block_c: int, block_n: int,
           interpret: bool):
    """Tile sizes and padded extents ``(block_c, block_n, Ncp, Np)``.

    Interpret mode takes any tiling (the tests sweep straddling and
    degenerate tiles).  Compiled for the TPU, every block must satisfy
    Mosaic's rule: a dim that is tiled is a multiple of 128 (lanes:
    candidates, and the class axis of the SMEM column block) and of 8
    (sublanes: class rows of the fill tile), and an untiled one equals
    the padded array's.
    """
    if interpret:
        block_c = min(block_c, n_cand)
        block_n = min(block_n, n_cls)
    else:
        block_c = (n_cand if block_c >= n_cand
                   else _round_up(block_c, 128))
        block_n = (_round_up(n_cls, ROW_GROUP) if block_n >= n_cls
                   else _round_up(block_n, 128))
    return (block_c, block_n, _round_up(n_cand, block_c),
            _round_up(n_cls, block_n))


def _kernel(cols_ref, scal_ref, cand_ref,
            fill_ref, obj_ref, best_ref, rho_ref,
            cum_scr, sacc_scr, pacc_scr, bobj_scr, brho_scr, bidx_scr,
            *, n_cblocks, n_blocks, block_c, block_n, group):
    ci = pl.program_id(1)
    ji = pl.program_id(2)

    @pl.when((ci == 0) & (ji == 0))
    def _init_best():
        bobj_scr[...] = jnp.full_like(bobj_scr, -jnp.inf)
        brho_scr[...] = jnp.zeros_like(brho_scr)
        bidx_scr[...] = jnp.zeros_like(bidx_scr)

    @pl.when(ji == 0)
    def _init_acc():
        cum_scr[...] = jnp.zeros_like(cum_scr)
        sacc_scr[...] = jnp.zeros_like(sacc_scr)
        pacc_scr[...] = jnp.zeros_like(pacc_scr)

    cand = cand_ref[0]                                # (1, BC)
    spare = scal_ref[0, 0, 0]
    zero = jnp.zeros((), cand.dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, (group, block_c), 0)

    # Column-by-column greedy fill, seeded from the cross-tile carries.
    # This is ref._scan_accumulators' recurrence verbatim: admit
    # (masked classes have incm = 0 so the validity mask is already
    # folded in), advance the running admitted sum, clip against the
    # remaining slack, fold into the sum/p accumulators.  Sequential
    # per-column adds keep the accumulation order identical to the
    # reference at any tiling; `group` columns are unrolled per step so
    # their fill rows leave in one aligned store.
    def _columns(g, carry):
        cum, sacc, pacc = carry
        base = g * jnp.int32(group)
        tile = jnp.zeros((group, block_c), cand.dtype)
        for k in range(group):
            j = base + k
            inc = jnp.where(cols_ref[0, 0, j] >= cand, cols_ref[0, 1, j],
                            zero)
            cum = cum + inc
            fill = jnp.clip(spare - (cum - inc), 0.0, inc)
            tile = jnp.where(rows == k, fill, tile)
            sacc = sacc + fill
            pacc = pacc + fill * cols_ref[0, 2, j]
        fill_ref[0, pl.ds(pl.multiple_of(base, group), group), :] = tile
        return cum, sacc, pacc

    cum, sacc, pacc = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(block_n // group), _columns,
        (cum_scr[...], sacc_scr[...], pacc_scr[...]))
    cum_scr[...] = cum
    sacc_scr[...] = sacc
    pacc_scr[...] = pacc

    @pl.when(ji == n_blocks - 1)
    def _pick():
        # (P5) objective of this candidate tile, then fold into the
        # running argmax.  Inside the tile the first maximum wins (the
        # lowest column holding the max); across tiles strictly-greater
        # keeps the earliest one — together jnp.argmax's semantics.
        rho_bar = scal_ref[0, 0, 1]
        sum_r_low = scal_ref[0, 0, 2]
        p_r_low = scal_ref[0, 0, 3]
        const = scal_ref[0, 0, 4]
        obj = ((cand - rho_bar) * (sum_r_low + sacc_scr[...])
               + (p_r_low + pacc_scr[...]) - const)
        obj_ref[0] = obj
        lane = jax.lax.broadcasted_iota(jnp.int32, obj.shape, 1)
        tile_max = jnp.max(obj, axis=1, keepdims=True)          # (1, 1)
        tile_best = jnp.min(
            jnp.where(obj == tile_max, lane, jnp.int32(block_c)),
            axis=1, keepdims=True)
        tile_rho = jnp.max(jnp.where(lane == tile_best, cand, -jnp.inf),
                           axis=1, keepdims=True)
        better = tile_max > bobj_scr[...]
        bidx_scr[...] = jnp.where(
            better, ci * jnp.int32(block_c) + tile_best, bidx_scr[...])
        brho_scr[...] = jnp.where(better, tile_rho, brho_scr[...])
        bobj_scr[...] = jnp.maximum(bobj_scr[...], tile_max)

    @pl.when((ci == n_cblocks - 1) & (ji == n_blocks - 1))
    def _final():
        best_ref[0] = bidx_scr[...]
        rho_ref[0] = brho_scr[...]


def fused_iter_call(bids_sorted, inc_max_sorted, p_sorted, cand,
                    spare, rho_bar, sum_r_low, p_r_low, const, *,
                    block_c=512, block_n=512, interpret=False):
    """The raw kernel launch behind :func:`fused_iter_sweep`.

    Same inputs as :func:`fused_iter_sweep`; the fill comes back in the
    kernel's class-major layout, trimmed: ``fill_cm`` is (B, N, Nc).
    Returns ``(fill_cm, obj, best, rho)``.
    """
    B, N = bids_sorted.shape
    Nc = cand.shape[1]
    dt = bids_sorted.dtype
    block_c, block_n, Ncp, Np = tiling(Nc, N, block_c, block_n, interpret)
    n_cblocks = Ncp // block_c
    n_blocks = Np // block_n
    group = ROW_GROUP if block_n % ROW_GROUP == 0 else 1
    # candidate padding replicates the last real column (rho_hat): a
    # duplicate ties, never strictly wins, so `best` stays a real index
    cand_p = jnp.pad(cand, ((0, 0), (0, Ncp - Nc)), mode="edge")[:, None, :]
    # padded classes are inert: inc_max = 0 kills their fill regardless
    # of how the padded bid compares to any candidate
    cols = jnp.pad(jnp.stack([bids_sorted, inc_max_sorted, p_sorted], axis=1),
                   ((0, 0), (0, 0), (0, Np - N)))            # (B, 3, Np)
    scal = jnp.stack([spare, rho_bar, sum_r_low, p_r_low, const],
                     axis=1).astype(dt)[:, None, :]          # (B, 1, 5)

    smem = pltpu.SMEM
    fill, obj, best, rho = pl.pallas_call(
        functools.partial(_kernel, n_cblocks=n_cblocks, n_blocks=n_blocks,
                          block_c=block_c, block_n=block_n, group=group),
        grid=(B, n_cblocks, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 3, block_n),
                         lambda bi, ci, ji: (bi, I32_ZERO, ji),
                         memory_space=smem),
            pl.BlockSpec((1, 1, 5),
                         lambda bi, ci, ji: (bi, I32_ZERO, I32_ZERO),
                         memory_space=smem),
            pl.BlockSpec((1, 1, block_c),
                         lambda bi, ci, ji: (bi, I32_ZERO, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n, block_c),
                         lambda bi, ci, ji: (bi, ji, ci)),
            pl.BlockSpec((1, 1, block_c),
                         lambda bi, ci, ji: (bi, I32_ZERO, ci)),
            pl.BlockSpec((1, 1, 1),
                         lambda bi, ci, ji: (bi, I32_ZERO, I32_ZERO)),
            pl.BlockSpec((1, 1, 1),
                         lambda bi, ci, ji: (bi, I32_ZERO, I32_ZERO)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Np, Ncp), dt),
            jax.ShapeDtypeStruct((B, 1, Ncp), dt),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), dt),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_c), dt)] * 3
        + [pltpu.VMEM((1, 1), dt)] * 2 + [pltpu.VMEM((1, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(cols, scal, cand_p)
    return fill[:, :N, :Nc], obj[:, 0, :Nc], best[:, 0, 0], rho[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("block_c", "block_n",
                                             "interpret"))
def fused_iter_sweep(bids_sorted, inc_max_sorted, p_sorted, cand,
                     spare, rho_bar, sum_r_low, p_r_low, const, *,
                     block_c=512, block_n=512, interpret=False):
    """One-launch fill/objective/argmax middle of an Alg. 4.1 iteration.

    Grid ``(B, Nc/BC, N/BN)``: batch parallel, candidate and class axes
    sequential (both carry scratch).  Inputs are the greedy-order
    invariants of ``ref.prepare`` plus the per-iteration bids/candidates.

    Parameters
    ----------
    bids_sorted : jnp.ndarray
        (B, N) effective bids in greedy (p-descending) order.
    inc_max_sorted : jnp.ndarray
        (B, N) fill headroom per class in greedy order (0 when masked).
    p_sorted : jnp.ndarray
        (B, N) masked unit penalty-rates in greedy order.
    cand : jnp.ndarray
        (B, Nc) candidate prices (bids + the (P5e) interval ends; the
        last column must be the largest-price end ``rho_hat`` — padding
        replicates it).
    spare : jnp.ndarray
        (B,) slack capacity shared by every candidate.
    rho_bar : jnp.ndarray
        (B,) on-demand floor price (objective reference).
    sum_r_low : jnp.ndarray
        (B,) total guaranteed allocation.
    p_r_low : jnp.ndarray
        (B,) p-weighted guaranteed allocation.
    const : jnp.ndarray
        (B,) constant objective term ``sum(p * r_up)``.
    block_c : int, optional
        Candidate-axis tile size (rounded up to a multiple of 128 when
        compiled for the TPU, see :func:`tiling`).
    block_n : int, optional
        Class-axis tile size (same rounding).
    interpret : bool, optional
        Run in Pallas interpret mode (the off-TPU path).

    Returns
    -------
    fill : jnp.ndarray
        (B, Nc, N) greedy slack fill of every candidate (greedy order).
    obj : jnp.ndarray
        (B, Nc) the (P5) objective of every candidate.
    best : jnp.ndarray
        (B,) int32 winning candidate index (first maximum).
    rho : jnp.ndarray
        (B,) winning candidate price.
    """
    fill_cm, obj, best, rho = fused_iter_call(
        bids_sorted, inc_max_sorted, p_sorted, cand, spare, rho_bar,
        sum_r_low, p_r_low, const, block_c=block_c, block_n=block_n,
        interpret=interpret)
    return jnp.swapaxes(fill_cm, 1, 2), obj, best, rho
