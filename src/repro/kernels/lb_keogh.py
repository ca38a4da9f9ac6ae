"""Pallas TPU kernel: LB_Keogh — per-candidate DTW lower bound.

After node-level pruning (``lb_isax`` on envelope summaries), DTW exact
search still pays O(n·band) per surviving candidate.  LB_Keogh orders and
prunes candidates first:

    LB(q, x) = sqrt( Σ_i  max(0, x_i − U_i, L_i − x_i)² )   ≤ DTW(q, x)

with (U, L) the query's upper/lower envelope over the warping band.  Pure
VPU elementwise + row reduction over a ``(block_b, n)`` tile — the same
memory-bound profile as ``lb_isax`` but at full resolution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _run_max(x: jax.Array, span: int, forward: bool) -> jax.Array:
    """Max of the ``span`` lanes starting at each lane (``forward``:
    ``[i, i+span-1]``; backward: ``[i-span+1, i]``), lanes outside the tile
    counting as ``-inf``.  Doubling steps of a lane roll + edge mask: no
    cummax, no lane-splitting reshape (neither lowers on the TPU)."""
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    neg = jnp.float32(-jnp.inf)

    def shifted(a, k):                 # forward: a[i+k]; backward: a[i-k]
        if k >= n:
            return jnp.full_like(a, neg)
        if forward:
            return jnp.where(lane < n - k, pltpu.roll(a, n - k, a.ndim - 1),
                             neg)
        return jnp.where(lane >= k, pltpu.roll(a, k, a.ndim - 1), neg)

    p = 1
    while 2 * p <= span:
        x = jnp.maximum(x, shifted(x, p))
        p *= 2
    return x if p == span else jnp.maximum(x, shifted(x, span - p))


def _wmax(x: jax.Array, r: int) -> jax.Array:
    """Edge-clamped sliding-window max (window ``[i-r, i+r]``) over the last
    axis of a ``(TB, n)`` tile, the union of the forward and backward
    ``r+1``-lane runs — same contract as :func:`repro.core.lb._window_max`
    (kept local: kernels stay leaf modules with no ``core`` imports).  Max
    is exact, so the result is bitwise that of the jnp twin."""
    if r <= 0:
        return x
    return jnp.maximum(_run_max(x, r + 1, True), _run_max(x, r + 1, False))


def _improved_kernel(r, x_ref, q_ref, u_ref, l_ref, o_ref):
    x = x_ref[...]                   # (TB, n)
    q = q_ref[...]                   # (1, n)
    U = u_ref[...]
    L = l_ref[...]
    above = jnp.maximum(x - U, 0.0)
    below = jnp.maximum(L - x, 0.0)
    d1 = jnp.maximum(above, below)   # first pass: LB_Keogh(x | env(q))
    h = jnp.clip(x, L, U)            # projection of x onto the envelope
    Uh = _wmax(h, r)                 # second pass: LB_Keogh(q | env(h))
    Lh = -_wmax(-h, r)
    d2 = jnp.maximum(jnp.maximum(q - Uh, 0.0), jnp.maximum(Lh - q, 0.0))
    o_ref[...] = (d1 * d1).sum(axis=-1, keepdims=True) \
        + (d2 * d2).sum(axis=-1, keepdims=True)


def _kernel(x_ref, u_ref, l_ref, o_ref):
    x = x_ref[...]                   # (TB, n)
    U = u_ref[...]                   # (1, n)
    L = l_ref[...]
    above = jnp.maximum(x - U, 0.0)
    below = jnp.maximum(L - x, 0.0)
    d = jnp.maximum(above, below)
    o_ref[...] = (d * d).sum(axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def lb_keogh(x: jax.Array, U: jax.Array, L: jax.Array, *, block_b: int = 256,
             interpret: bool = False) -> jax.Array:
    """``x [B, n]`` candidates, ``U/L [n]`` query envelope → squared LB [B]."""
    B, n = x.shape
    Bp = -(-B // block_b) * block_b
    xp = jnp.pad(x.astype(jnp.float32), ((0, Bp - B), (0, 0)))
    Up = U.astype(jnp.float32)[None, :]
    Lp = L.astype(jnp.float32)[None, :]
    out = pl.pallas_call(
        _kernel,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        interpret=interpret,
    )(xp, Up, Lp)
    return out[:B, 0]


@functools.partial(jax.jit,
                   static_argnames=("r", "block_b", "interpret"))
def lb_improved(x: jax.Array, q: jax.Array, U: jax.Array, L: jax.Array, *,
                r: int, block_b: int = 256,
                interpret: bool = False) -> jax.Array:
    """Squared LB_Improved (Lemire 2009): ``x [B, n]`` candidates, ``q [n]``
    query, ``U/L [n]`` its envelope, band radius ``r`` → squared LB [B].

    ``LB_Improved² = LB_Keogh²(x | env(q)) + LB_Keogh²(q | env(h))`` with
    ``h = clip(x, L, U)`` the envelope projection of the candidate.  Both
    terms are banded-L2 slacks of disjoint alignment deficits, so the
    squared forms add and the sum still lower-bounds DTW² while dominating
    plain LB_Keogh.  One fused tile: no second kernel launch for the
    reverse pass.
    """
    B, n = x.shape
    Bp = -(-B // block_b) * block_b
    xp = jnp.pad(x.astype(jnp.float32), ((0, Bp - B), (0, 0)))
    qp = q.astype(jnp.float32)[None, :]
    Up = U.astype(jnp.float32)[None, :]
    Lp = L.astype(jnp.float32)[None, :]
    out = pl.pallas_call(
        functools.partial(_improved_kernel, int(r)),
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        interpret=interpret,
    )(xp, qp, Up, Lp)
    return out[:B, 0]
