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


#: most candidates a query may bring into :func:`topk_merge_cutoff` for
#: insertion rounds; beyond it the sort of :func:`topk_merge` runs.  On a
#: v5e (Q 64, C 2,048) m rounds cost 6 + 2.1·m µs and the sort 86 µs
#: (PERF.md §6), so rounds win up to m ≈ 38
MERGE_ROUNDS = 32

_I32_MAX = 0x7FFFFFFF


def _flip(b: jax.Array) -> jax.Array:
    """f32 bits ↔ an i32 key with the total order ``lax.top_k`` sorts by
    (−0.0 below +0.0, NaNs outside ±inf); the map is its own inverse."""
    return b ^ ((b >> 31) & _I32_MAX)


def _order_key(x: jax.Array) -> jax.Array:
    return _flip(jax.lax.bitcast_convert_type(x, jnp.int32))


def topk_merge_cutoff(topd: jax.Array, topi: jax.Array, d2: jax.Array,
                      ids: jax.Array, axis_name: str | None = None
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`topk_merge` at a cost that follows what the candidates change.

    ``ids [C]`` are the slab's row ids, shared by every query; a candidate
    at ``±inf`` takes id ``-1``.  The merged ``(topd, topi)`` is bitwise
    ``topk_merge(topd, topi, d2, where(isinf(d2), -1, ids))``, and the
    third output ``m`` is the most candidates any query brings in (capped
    at ``k``).

    ``top_k`` is stable and the held entries come first, so a candidate
    enters iff it lies strictly below its query's held ``k``-th entry, and
    it lands after every held entry it does not lie below.  With ``m == 0``
    nothing changes.  With ``m <= MERGE_ROUNDS`` each of ``m`` rounds
    takes every query's least remaining candidate (the lowest column on
    ties) and inserts it by compare-and-shift.  Beyond that the sort runs.
    Under ``jax.vmap(..., axis_name=axis_name)`` the branch and the round
    count follow the largest ``m`` over the mapped axis, so that the map
    keeps a real conditional (a batched one would run every branch, the
    sort included); every branch gives the same result, and ``m`` stays
    the member's own."""
    Q, k = topd.shape
    C = d2.shape[1]
    ids = jnp.asarray(ids)
    cut = _order_key(topd[:, k - 1:])
    # the keys are recomputed where they are used, inside the rounds, so
    # that the conditional takes d2 alone and no [Q, C] temporary
    m = jnp.minimum((_order_key(d2) < cut).sum(axis=1, dtype=jnp.int32)
                    .max(), k)
    m_all = m if axis_name is None else jax.lax.pmax(m, axis_name)

    def keep():
        return topd, topi

    def insert():
        col = jnp.arange(C, dtype=jnp.int32)[None, :]
        slot = jnp.arange(k, dtype=jnp.int32)[None, :]

        def round_(_, c):
            td, ti, lk, lc = c
            dk = _order_key(d2)
            # candidates after the last one taken, in (key, column) order
            after = (dk > lk[:, None]) | ((dk == lk[:, None])
                                          & (col > lc[:, None]))
            key = jnp.where((dk < cut) & after, dk, _I32_MAX)
            j = jnp.argmin(key, axis=1).astype(jnp.int32)   # first on ties
            vk = key.min(axis=1)
            v = jax.lax.bitcast_convert_type(_flip(vk), jnp.float32)
            vi = jnp.where(jnp.isinf(v), -1, ids[j])
            tk = _order_key(td)
            pos = (tk <= vk[:, None]).sum(axis=1, dtype=jnp.int32)[:, None]
            take = (vk < tk[:, k - 1])[:, None]

            def ins(t, x):
                prev = jnp.concatenate([t[:, :1], t[:, :-1]], axis=1)
                new = jnp.where(slot < pos, t,
                                jnp.where(slot == pos, x[:, None], prev))
                return jnp.where(take, new, t)

            return ins(td, v), ins(ti, vi), vk, j

        init = (topd, topi, jnp.full((Q,), -_I32_MAX - 1, jnp.int32),
                jnp.full((Q,), -1, jnp.int32))
        td, ti, _, _ = jax.lax.fori_loop(0, m_all, round_, init)
        return td, ti

    def sort():
        idt = jnp.where(jnp.isinf(d2), -1,
                        jnp.broadcast_to(ids[None, :], d2.shape))
        return topk_merge(topd, topi, d2, idt)

    branch = jnp.where(m_all == 0, 0, jnp.where(m_all <= MERGE_ROUNDS, 1, 2))
    topd, topi = jax.lax.switch(branch, (keep, insert, sort))
    return topd, topi, m
