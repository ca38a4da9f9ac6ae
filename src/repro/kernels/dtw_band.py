"""Pallas TPU kernel: masked banded DTW — the fused DP of the DTW search
paths (ROADMAP: batched DTW exact search end-to-end).

After leaf/span pruning (``lb_paa_interval`` on envelope summaries) and the
candidate-level LB_Keogh pre-filter, the surviving candidates pay the exact
banded DP.  This kernel fuses mask + cutoff + DP so pruned candidates skip
the work instead of paying it under a where-mask:

* the DP walks the ``2n-1`` anti-diagonals (cells of diagonal ``d`` depend
  only on diagonals ``d-1``/``d-2``), so the sequential depth is O(n) and
  every step is one VPU-shaped ``(n, block_m)`` update held in registers/
  VMEM — no HBM traffic between diagonals;
* the per-query ``while_loop`` exits as soon as every lane in the tile is
  dead: a lane starts dead when its LB_Keogh mask is off, and dies when the
  min DP value over its last two diagonals exceeds the cutoff τ² (every
  warping path crosses a cell of diagonal ``d`` or ``d-1`` and path values
  only grow, so the final distance is bounded below by that min);
* tiles whose mask is entirely off are skipped wholesale via ``pl.when``.

Tile layout (every block obeys the TPU's (8, 128) rule): candidates ride
the 128 lanes, DP rows the sublanes.  Slot ``i`` of diagonal ``d`` is cell
``(i, d - i)``, so the query value of a slot is fixed and the candidate is
the one that shifts: the wrapper hands the kernel each candidate *reversed*
and zero-padded to ``P >= 2n-1`` rows, and one dynamic sublane roll per
diagonal lines ``x[d - i]`` up with row ``i``.  Queries come 8 to a grid
step; each walks its own ``while_loop`` over the shared candidate tile.
Per-candidate state (mask, liveness, result) is a ``(1, block_m)`` row.

Masked / abandoned lanes come back ``+inf`` — exactly the convention the
top-k merge consumes.  Every cell is the same f32 ``cost + min(three
neighbours)`` as the jnp twin (``core.lb.dtw2_masked_batch_jnp``, which
off-TPU callers use through ``ops.dtw_band``), so the two agree bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QB = 8      # queries per grid step: the sublane tile of the mask/out blocks


def _kernel(qt_ref, xr_ref, mask_ref, cut_ref, o_ref, dm2_ref, dm1_ref, *,
            n: int, r: int):
    P, bm = xr_ref.shape
    INF = jnp.float32(jnp.inf)
    qt = qt_ref[0]                        # (n, QB): query qi in lane qi
    slot = jax.lax.broadcasted_iota(jnp.int32, (n, bm), 0)     # DP row i
    qlane = jax.lax.broadcasted_iota(jnp.int32, (n, QB), 1)
    o_ref[...] = jnp.full((QB, bm), INF)

    @pl.when(jnp.max(mask_ref[...]) > 0.0)
    def _():
        def per_query(qi, carry):
            # lane select + reduce: the query as a column, no transpose
            qcol = jnp.sum(jnp.where(qlane == qi, qt, 0.0), axis=1,
                           keepdims=True)                       # (n, 1)
            alive0 = mask_ref[pl.ds(qi, 1), :]                  # (1, bm)
            cut = cut_ref[pl.ds(qi, 1), :]                      # (1, bm)
            # the two carried diagonals live in VMEM scratch: a splat
            # constant as a loop carry cannot be relaid out by Mosaic
            dm2_ref[...] = jnp.full((n, bm), INF)
            dm1_ref[...] = jnp.full((n, bm), INF)

            def cond(c):
                d, alive = c
                return (d < 2 * n - 1) & (jnp.max(alive) > 0.0)

            def body(c):
                d, alive = c
                dm2 = dm2_ref[...]
                dm1 = dm1_ref[...]
                # roll(xr, s)[i] = xr[i - s] = x[d - i] for s = d - n + 1
                s = d + (P - n + 1)
                s = jnp.where(s >= P, s - P, s)
                xd = pltpu.roll(xr_ref[...], s, 0)[:n]          # (n, bm)
                cost = (xd - qcol) ** 2                         # cost(i, d-i)
                up = jnp.where(slot == 0, INF, pltpu.roll(dm1, 1, 0))
                diag = jnp.where(slot == 0, INF, pltpu.roll(dm2, 1, 0))
                best = jnp.minimum(jnp.minimum(dm1, up), diag)
                best = jnp.where((slot == 0) & (d == 0), 0.0, best)
                j = d - slot
                inband = (j >= 0) & (j < n) & (jnp.abs(slot - j) <= r)
                out = jnp.where(inband, cost + best, INF)
                lane_min = jnp.minimum(out.min(axis=0, keepdims=True),
                                       dm1.min(axis=0, keepdims=True))
                dm2_ref[...] = dm1
                dm1_ref[...] = out
                return d + 1, jnp.where(lane_min <= cut, alive, 0.0)

            _, alive = jax.lax.while_loop(cond, body, (jnp.int32(0), alive0))
            # cell (n-1, n-1) is slot n-1 of the last diagonal 2n-2
            o_ref[pl.ds(qi, 1), :] = jnp.where(
                alive > 0.0, dm1_ref[pl.ds(n - 1, 1), :], INF)
            return carry

        jax.lax.fori_loop(0, QB, per_query, 0)


@functools.partial(jax.jit,
                   static_argnames=("r", "block_m", "interpret"))
def dtw_band(qs: jax.Array, xs: jax.Array, mask: jax.Array,
             cutoff2: jax.Array, *, r: int, block_m: int = 128,
             interpret: bool = False) -> jax.Array:
    """Masked banded DTW²: ``qs [Q, n]``, ``xs [m, n]``, ``mask [Q, m]``,
    ``cutoff2 [Q]`` → squared distances ``[Q, m] f32`` (``+inf`` on masked /
    abandoned / padded lanes).  Grid: (8-query block, candidate block)."""
    Q, n = qs.shape
    m = xs.shape[0]
    Qp = -(-Q // QB) * QB
    mp = -(-m // block_m) * block_m
    P = -(-(2 * n - 1) // 8) * 8
    qs_p = jnp.pad(qs.astype(jnp.float32), ((0, Qp - Q), (0, 0)))
    qt = qs_p.reshape(Qp // QB, QB, n).transpose(0, 2, 1)    # [Qp/8, n, 8]
    xs_p = jnp.pad(xs.astype(jnp.float32), ((0, mp - m), (0, 0)))
    xr = jnp.pad(jnp.flip(xs_p, axis=1).T, ((0, P - n), (0, 0)))  # [P, mp]
    mask_p = jnp.pad(mask.astype(jnp.float32), ((0, Qp - Q), (0, mp - m)))
    cut = jnp.broadcast_to(
        jnp.pad(cutoff2.astype(jnp.float32), (0, Qp - Q))[:, None],
        (Qp, block_m))

    grid = (Qp // QB, mp // block_m)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, r=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, QB), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((P, block_m), lambda i, j: (0, j)),
            pl.BlockSpec((QB, block_m), lambda i, j: (i, j)),
            pl.BlockSpec((QB, block_m), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((QB, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, mp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, block_m), jnp.float32)] * 2,
        interpret=interpret,
    )(qt, xr, mask_p, cut)
    return out[:Q, :m]
