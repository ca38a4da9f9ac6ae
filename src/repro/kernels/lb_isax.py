"""Pallas TPU kernel: MINDIST(PAA, iSAX region) over the whole node table —
the exact-search pruning scan (paper §5.5).

Inputs are the query PAA block and the *precomputed* per-node region bounds
(``lo/hi [L, w]``, materialized once at index build — this moves the
breakpoint gathers out of the kernel entirely, DESIGN.md §2).  Each grid step
loads a ``(TL, w)`` strip of the node table plus a ``(TQ, w)`` strip of
queries and emits the ``(TQ, TL)`` squared-bound tile.

VMEM at defaults (TQ=8, TL=512, w=16): operands ~70 KB, the broadcast
intermediate ``(TQ, TL, w)`` f32 = 256 KB — small; the scan is memory-bound
on the node table read, which is the point: Dumpy's compactness (fewer
leaves) is a direct multiplier on this kernel's runtime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(qlo_ref, qhi_ref, lo_ref, hi_ref, o_ref, *, scale: float):
    qlo = qlo_ref[...]            # (TQ, w) query interval (ED: qlo == qhi)
    qhi = qhi_ref[...]
    lo = lo_ref[...]              # (TL, w)
    hi = hi_ref[...]              # (TL, w)
    below = jnp.maximum(lo[None, :, :] - qhi[:, None, :], 0.0)
    above = jnp.maximum(qlo[:, None, :] - hi[None, :, :], 0.0)
    d = jnp.maximum(below, above)
    o_ref[...] = scale * (d * d).sum(-1)


@functools.partial(jax.jit, static_argnames=("n", "tq", "tl", "interpret"))
def lb_paa_interval(seg_lo: jax.Array, seg_hi: jax.Array, lo: jax.Array,
                    hi: jax.Array, *, n: int, tq: int = 8, tl: int = 512,
                    interpret: bool = False) -> jax.Array:
    """Interval MINDIST: query intervals ``seg_lo/seg_hi [Q, w]`` vs regions
    ``lo/hi [L, w]`` → squared bound ``[Q, L] f32``.

    The metric-generic region bound (see ``core.metric``): with a degenerate
    interval it is the ED MINDIST; with the LB_Keogh envelope summary it is
    the DTW envelope bound — same kernel body, one extra operand strip.

    Padding: queries pad with zeros; node rows pad with ``lo=+big, hi=+big``
    so padded rows produce huge bounds (never selected); sliced off anyway.
    """
    Q, w = seg_lo.shape
    L = lo.shape[0]
    Qp, Lp = -(-Q // tq) * tq, -(-L // tl) * tl
    qlo_p = jnp.pad(seg_lo.astype(jnp.float32), ((0, Qp - Q), (0, 0)))
    qhi_p = jnp.pad(seg_hi.astype(jnp.float32), ((0, Qp - Q), (0, 0)))
    big = jnp.float32(3e9)
    lo_p = jnp.pad(lo.astype(jnp.float32), ((0, Lp - L), (0, 0)),
                   constant_values=big)
    hi_p = jnp.pad(hi.astype(jnp.float32), ((0, Lp - L), (0, 0)),
                   constant_values=big)

    grid = (Qp // tq, Lp // tl)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=n / w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, w), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, w), lambda i, j: (i, 0)),
            pl.BlockSpec((tl, w), lambda i, j: (j, 0)),
            pl.BlockSpec((tl, w), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tq, tl), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Lp), jnp.float32),
        interpret=interpret,
    )(qlo_p, qhi_p, lo_p, hi_p)
    return out[:Q, :L]


@functools.partial(jax.jit, static_argnames=("n", "tq", "tl", "interpret"))
def lb_isax(paa_q: jax.Array, lo: jax.Array, hi: jax.Array, *, n: int,
            tq: int = 8, tl: int = 512, interpret: bool = False) -> jax.Array:
    """``paa_q [Q, w]``, ``lo/hi [L, w]`` → squared MINDIST ``[Q, L] f32``
    — the degenerate-interval case of :func:`lb_paa_interval` (bitwise
    identical to the historical ED-only kernel)."""
    return lb_paa_interval(paa_q, paa_q, lo, hi, n=n, tq=tq, tl=tl,
                           interpret=interpret)
