"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend every wrapper runs the compiled Pallas kernel.  Off-TPU
(CPU test runs) a wrapper either runs the kernel body in Pallas interpret
mode or, where one fused XLA program is much faster than interpreting the
grid, the kernel's jnp twin.  The kernels themselves default to
``interpret=False``; callers of this module never pass it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import dtw_band as _dtw
from . import lb_isax as _lb
from . import lb_keogh as _lbk
from . import pairwise_l2 as _pl2
from . import sax_encode as _se


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def sax_encode(x: jax.Array, w: int, b: int) -> tuple[jax.Array, jax.Array]:
    """Fused PAA+SAX (Stage 1 of Algorithm 1).  ``[B, n] → (f32 [B,w], i32 [B,w])``."""
    return _se.sax_encode(x, w=w, b=b, interpret=_interpret())


def pairwise_l2(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared distance matrix ``[Q, X]`` (candidate verification)."""
    return _pl2.pairwise_l2(q, x, interpret=_interpret())


def lb_isax(paa_q: jax.Array, lo: jax.Array, hi: jax.Array, n: int) -> jax.Array:
    """Squared MINDIST to every leaf pack ``[Q, L]`` (pruning scan).

    On TPU this is the Pallas kernel; elsewhere the fused-jnp oracle
    (``mindist_jnp``) — one XLA program beats interpreting the kernel grid in
    Python on CPU."""
    if _interpret():
        from repro.core.lb import mindist_jnp
        return mindist_jnp(paa_q, lo, hi, n)
    return _lb.lb_isax(paa_q, lo, hi, n=n, interpret=False)


def lb_paa_interval(seg_lo: jax.Array, seg_hi: jax.Array, lo: jax.Array,
                    hi: jax.Array, n: int) -> jax.Array:
    """Squared interval MINDIST ``[Q, L]`` — the metric-generic pruning
    scan: ED feeds the degenerate interval (PAA, PAA), DTW the LB_Keogh
    envelope summary (see ``core.metric``).  Pallas on TPU, fused-jnp
    oracle elsewhere."""
    if _interpret():
        from repro.core.lb import lb_interval_jnp
        return lb_interval_jnp(seg_lo, seg_hi, lo, hi, n)
    return _lb.lb_paa_interval(seg_lo, seg_hi, lo, hi, n=n, interpret=False)


def lb_keogh(x: jax.Array, U: jax.Array, L: jax.Array) -> jax.Array:
    """Squared LB_Keogh per candidate (DTW pre-filter, cascade stage 1)."""
    return _lbk.lb_keogh(x, U, L, interpret=_interpret())


def lb_improved(x: jax.Array, q: jax.Array, U: jax.Array, L: jax.Array,
                r: int) -> jax.Array:
    """Squared LB_Improved per candidate (cascade stage 2: second-pass
    envelope of the LB_Keogh projection; dominates ``lb_keogh`` and still
    lower-bounds DTW²).  Pallas kernel on TPU; off-TPU the batched jnp
    twin — one fused XLA program beats interpreting the grid on CPU."""
    if _interpret():
        from repro.core.lb import lb_improved2_batch_jnp
        return lb_improved2_batch_jnp(
            x, q[None, :], U[None, :], L[None, :], r)[0]
    return _lbk.lb_improved(x, q, U, L, r=r, interpret=False)


def dtw_band(qs: jax.Array, xs: jax.Array, mask: jax.Array,
             cutoff2: jax.Array, r: int) -> jax.Array:
    """Masked banded DTW² ``[Q, m]`` with cutoff early-abandon — the final
    stage of the LB_Keogh → LB_Improved → DP cascade (``mask`` arrives with
    both LB stages already applied, so only cascade survivors pay the
    O(n·band) DP).  Pallas kernel on TPU; off-TPU the jnp anti-diagonal
    twin (one XLA while_loop, same masking semantics)."""
    if _interpret():
        from repro.core.lb import dtw2_masked_batch_jnp
        return dtw2_masked_batch_jnp(qs, xs, r, mask, cutoff2)
    return _dtw.dtw_band(qs, xs, mask, cutoff2, r=r, interpret=False)


def knn_from_leaves(q: jax.Array, db_ordered: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k over a contiguous candidate slab: distances via the Pallas
    kernel, selection via ``lax.top_k``.  Returns (ordered-position ids, d2)."""
    d2 = pairwise_l2(q[None, :], db_ordered)[0]
    neg, idx = jax.lax.top_k(-d2, min(k, d2.shape[0]))
    return idx, -neg


@jax.jit
def topk_merge(topd: jax.Array, topi: jax.Array, d2: jax.Array,
               ids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused per-query top-k merge step of the batched search loop.

    ``topd/topi [Q, k]`` running best (squared dist, id); ``d2 [Q, C]`` new
    candidate distances with ``ids [Q, C]``.  Masked-out candidates must
    arrive as ``+inf``.  Returns the merged ``(topd, topi)``."""
    k = topd.shape[1]
    alld = jnp.concatenate([topd, d2], axis=1)
    alli = jnp.concatenate([topi, ids], axis=1)
    neg, sel = jax.lax.top_k(-alld, k)
    return -neg, jnp.take_along_axis(alli, sel, axis=1)
