"""Lower-bounding and true distance functions (ED + DTW).

The load-bearing invariant of the whole iSAX index family is::

    mindist_paa_isax(PAA(q), node) <= ED(q, s)   for every series s in node

which enables exact-search pruning (paper §5.5) — it is property-tested in
``tests/test_lb_properties.py``.  DTW support follows the iSAX-family
approach (paper §7 / MESSI [49]): an LB_Keogh-style envelope of the query is
summarized per segment and bounded against the node regions.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .sax import breakpoints_ext, isax_bounds_np


# ---------------------------------------------------------------------------
# Euclidean distance (true)
# ---------------------------------------------------------------------------

def ed_np(q: np.ndarray, xs: np.ndarray) -> np.ndarray:
    """Squared-free ED: ``q [n]``, ``xs [m, n]`` → ``[m]``."""
    d = xs - q[None, :]
    return np.sqrt((d * d).sum(axis=1))


@jax.jit
def ed2_batch_jnp(q: jax.Array, xs: jax.Array) -> jax.Array:
    """Squared ED, batched: ``q [Q, n]``, ``xs [m, n]`` → ``[Q, m]``.

    Uses the MXU-friendly ``|q|^2 + |x|^2 - 2 q·x`` form (same math as the
    Pallas ``pairwise_l2`` kernel; this is its oracle path).  The product
    runs at HIGHEST precision: the TPU's default f32 matmul rounds operands
    to bf16, an error far above the f32 ties the exact-search re-rank
    slack absorbs."""
    qn = (q * q).sum(axis=-1, keepdims=True)          # [Q, 1]
    xn = (xs * xs).sum(axis=-1)[None, :]              # [1, m]
    cross = jnp.matmul(q, xs.T,                       # [Q, m]  (MXU)
                       precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qn + xn - 2.0 * cross, 0.0)


# ---------------------------------------------------------------------------
# MINDIST(PAA(q), iSAX region)  — ED lower bound
# ---------------------------------------------------------------------------

def mindist_paa_bounds_np(paa_q: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                          n: int) -> np.ndarray:
    """ED lower bound between a query and everything inside a region.

    ``paa_q: [w]``; ``lo/hi: [..., w]`` region bounds → ``[...]`` distances.
    ``sqrt(n/w * sum_j d_j^2)`` with ``d_j = max(0, lo_j - paa_j, paa_j - hi_j)``.
    """
    w = paa_q.shape[-1]
    below = np.maximum(lo - paa_q, 0.0)
    above = np.maximum(paa_q - hi, 0.0)
    d = np.maximum(below, above)
    return np.sqrt((n / w) * (d * d).sum(axis=-1))


def node_bounds_np(sym: np.ndarray, card: np.ndarray, b: int,
                   clamp: float = 1e9) -> tuple[np.ndarray, np.ndarray]:
    """Finite (clamped) region bounds for node tables, ready for device use."""
    lo, hi = isax_bounds_np(sym, card, b)
    return (np.clip(lo, -clamp, clamp).astype(np.float32),
            np.clip(hi, -clamp, clamp).astype(np.float32))


@functools.partial(jax.jit, static_argnums=(3,))
def mindist_jnp(paa_q: jax.Array, lo: jax.Array, hi: jax.Array, n: int) -> jax.Array:
    """Batched MINDIST: ``paa_q [Q, w]``, ``lo/hi [L, w]`` → ``[Q, L]``
    (squared, to avoid sqrt in the pruning loop)."""
    return lb_interval_jnp(paa_q, paa_q, lo, hi, n)


@functools.partial(jax.jit, static_argnums=(4,))
def lb_interval_jnp(seg_lo: jax.Array, seg_hi: jax.Array, lo: jax.Array,
                    hi: jax.Array, n: int) -> jax.Array:
    """Interval MINDIST, batched + squared: query intervals
    ``seg_lo/seg_hi [Q, w]`` vs regions ``lo/hi [L, w]`` → ``[Q, L]``.

    The metric-generic region bound (see ``core.metric``): a degenerate
    interval (``seg_lo == seg_hi == PAA(q)``) gives the ED MINDIST, the
    LB_Keogh envelope summary gives the DTW bound — identical op order to
    the old ED-only ``mindist_jnp``, so ED results are bitwise unchanged."""
    w = seg_lo.shape[-1]
    below = jnp.maximum(lo[None, :, :] - seg_hi[:, None, :], 0.0)
    above = jnp.maximum(seg_lo[:, None, :] - hi[None, :, :], 0.0)
    d = jnp.maximum(below, above)
    return (n / w) * (d * d).sum(axis=-1)


# ---------------------------------------------------------------------------
# DTW (banded) + envelope lower bound
# ---------------------------------------------------------------------------

def dtw_envelope_np(q: np.ndarray, r: int) -> tuple[np.ndarray, np.ndarray]:
    """LB_Keogh envelope: ``U_i = max(q[i-r:i+r+1])``, ``L_i = min(...)``."""
    n = q.shape[0]
    idx = np.arange(n)
    lo_i = np.maximum(idx - r, 0)
    hi_i = np.minimum(idx + r + 1, n)
    U = np.array([q[a:z].max() for a, z in zip(lo_i, hi_i)])
    L = np.array([q[a:z].min() for a, z in zip(lo_i, hi_i)])
    return U, L


def envelope_paa_np(U: np.ndarray, L: np.ndarray, w: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-segment envelope summary that *preserves the bound*: the segment
    max of U and min of L (mean would break the lower-bound property)."""
    n = U.shape[0]
    return (U.reshape(w, n // w).max(axis=1), L.reshape(w, n // w).min(axis=1))


def mindist_dtw_bounds_np(U_seg: np.ndarray, L_seg: np.ndarray,
                          lo: np.ndarray, hi: np.ndarray, n: int) -> np.ndarray:
    """DTW lower bound of a query envelope vs. iSAX regions.

    ``d_j = max(0, lo_j - U_j, L_j - hi_j)`` — zero unless the node region is
    entirely above the envelope max or below the envelope min, so it lower
    bounds DTW for any warping inside the band (iSAX-DTW, MESSI [49]).
    """
    w = U_seg.shape[-1]
    below = np.maximum(lo - U_seg, 0.0)
    above = np.maximum(L_seg - hi, 0.0)
    d = np.maximum(below, above)
    return np.sqrt((n / w) * (d * d).sum(axis=-1))


def lb_keogh_np(xs: np.ndarray, U: np.ndarray, L: np.ndarray) -> np.ndarray:
    """Per-candidate LB_Keogh (DTW pre-filter): ``xs [m, n]`` → ``[m]``."""
    above = np.maximum(xs - U[None, :], 0.0)
    below = np.maximum(L[None, :] - xs, 0.0)
    d = np.maximum(above, below)
    return np.sqrt((d * d).sum(axis=1))


def dtw_np(a: np.ndarray, b_: np.ndarray, r: int) -> float:
    """Exact banded DTW (Sakoe–Chiba, window ``r``), host reference."""
    n, m = len(a), len(b_)
    INF = np.inf
    prev = np.full(m + 1, INF)
    prev[0] = 0.0
    for i in range(1, n + 1):
        cur = np.full(m + 1, INF)
        j_lo, j_hi = max(1, i - r), min(m, i + r)
        for j in range(j_lo, j_hi + 1):
            c = (a[i - 1] - b_[j - 1]) ** 2
            cur[j] = c + min(prev[j], prev[j - 1], cur[j - 1])
        prev = cur
    return float(np.sqrt(prev[m]))


def dtw_np_batch(qs: np.ndarray, cand: np.ndarray, r: int) -> np.ndarray:
    """:func:`dtw_np` vectorized over a per-query candidate set:
    ``qs [Q, n]``, ``cand [Q, kk, n]`` → ``[Q, kk]`` float64.

    Bitwise-identical per lane to the scalar reference (the DP recurrence is
    elementwise per lane and the i/j visit order is the same — numpy f64
    min/add are IEEE-exact), but the Python loop runs ``n·band`` times total
    instead of per candidate, which is what keeps the k-sized DTW host
    re-rank of the device search out of the profile (it used to cost more
    than a quarter of the batch-64 exact search)."""
    Q, kk, n = cand.shape
    # keep the input dtype: the scalar reference squares the difference in
    # the caller's f32 before the f64 DP add — promoting first drifts 1 ulp
    a = np.repeat(np.asarray(qs), kk, axis=0)                # [Q*kk, n]
    b_ = np.asarray(cand).reshape(Q * kk, n)
    INF = np.inf
    prev = np.full((Q * kk, n + 1), INF)
    prev[:, 0] = 0.0
    for i in range(1, n + 1):
        cur = np.full((Q * kk, n + 1), INF)
        j_lo, j_hi = max(1, i - r), min(n, i + r)
        for j in range(j_lo, j_hi + 1):
            c = (a[:, i - 1] - b_[:, j - 1]) ** 2
            cur[:, j] = c + np.minimum(
                np.minimum(prev[:, j], prev[:, j - 1]), cur[:, j - 1])
        prev = cur
    return np.sqrt(prev[:, n]).reshape(Q, kk)


def _dtw_scan(q: jax.Array, xs: jax.Array, r: int) -> jax.Array:
    """Banded DTW DP of one query vs a candidate batch (traceable body shared
    by the single-query and query-batched wrappers)."""
    n = q.shape[0]
    m = xs.shape[0]
    INF = jnp.float32(jnp.inf)
    jidx = jnp.arange(n)

    def row(prev, i):
        # prev: [m, n] DP row i-1 (prev[:, j] = D(i-1, j))
        cost = (xs[:, :] - q[i]) ** 2                      # [m, n] cost(i, j)
        in_band = jnp.abs(jidx - i) <= r                   # [n]
        prev_up = prev                                      # D(i-1, j)
        prev_diag = jnp.concatenate(
            [jnp.where(i == 0, 0.0, INF)[None] * jnp.ones((m, 1)), prev[:, :-1]], axis=1)

        def cell(carry, j):
            left = carry                                    # D(i, j-1), [m]
            best = jnp.minimum(jnp.minimum(prev_up[:, j], prev_diag[:, j]), left)
            val = jnp.where(in_band[j], cost[:, j] + best, INF)
            return val, val

        init_left = jnp.full((m,), INF)
        _, rows = jax.lax.scan(cell, init_left, jnp.arange(n))
        new = rows.T                                        # [m, n]
        return new, None

    prev0 = jnp.full((m, n), INF)
    last, _ = jax.lax.scan(row, prev0, jnp.arange(n))
    return jnp.sqrt(last[:, n - 1])


@functools.partial(jax.jit, static_argnums=(2,))
def dtw_batch_jnp(q: jax.Array, xs: jax.Array, r: int) -> jax.Array:
    """Banded DTW of one query vs a batch: ``q [n]``, ``xs [m, n]`` → ``[m]``.

    Row-wise DP via ``lax.scan``; each carried row is the full length-n
    frontier with out-of-band cells masked to +inf.  O(n^2) cells but
    vectorized over the candidate batch — the band mask keeps the *math*
    identical to the banded reference.
    """
    return _dtw_scan(q, xs, r)


@functools.partial(jax.jit, static_argnums=(2,))
def dtw_batch_queries_jnp(qs: jax.Array, xs: jax.Array, r: int,
                          mask: jax.Array | None = None) -> jax.Array:
    """Banded DTW of a *query batch* vs a candidate batch:
    ``qs [Q, n]``, ``xs [m, n]`` → ``[Q, m]`` — the row DP of
    :func:`dtw_batch_jnp` vmapped over queries (ROADMAP: batched DTW).

    ``mask [Q, m]`` is the LB_Keogh pre-filter hook: masked-out entries
    (``False``) come back as ``+inf``.  Under plain jnp the DP cost is still
    paid (XLA has no dynamic shapes); on TPU the same mask becomes the skip
    predicate of the fused while_loop kernel, which is why it threads through
    here rather than being applied by callers."""
    d = jax.vmap(lambda q: _dtw_scan(q, xs, r))(qs)
    if mask is not None:
        d = jnp.where(mask, d, jnp.inf)
    return d


@functools.partial(jax.jit, static_argnums=(1,))
def dtw_envelope_batch_jnp(qs: jax.Array, r: int) -> tuple[jax.Array, jax.Array]:
    """LB_Keogh envelopes for a query batch: ``qs [Q, n]`` → ``(U, L)``
    ``[Q, n]`` each — the batched :func:`dtw_envelope_np` (windowed max/min
    with edge clamping via ±inf padding)."""
    win = 2 * r + 1
    U = jax.lax.reduce_window(qs, -jnp.inf, jax.lax.max, (1, win), (1, 1),
                              [(0, 0), (r, r)])
    L = jax.lax.reduce_window(qs, jnp.inf, jax.lax.min, (1, win), (1, 1),
                              [(0, 0), (r, r)])
    return U, L


@jax.jit
def lb_keogh2_batch_jnp(xs: jax.Array, U: jax.Array, L: jax.Array) -> jax.Array:
    """Squared LB_Keogh of every candidate against every query envelope:
    ``xs [..., m, n]``, ``U/L [Q, n]`` → ``[Q, m]`` (one ``[Q, m, n]``
    temporary — callers chunk ``m`` at scale).  ``xs`` may also carry a
    leading per-query axis ``[Q, m, n]`` (the leaf-gather layout).

    The squared form is what the device pruning loops compare against their
    running squared top-k cutoffs (same convention as ``lb_interval_jnp``)."""
    xsb = xs if xs.ndim == 3 else xs[None, :, :]
    above = jnp.maximum(xsb - U[:, None, :], 0.0)
    below = jnp.maximum(L[:, None, :] - xsb, 0.0)
    d = jnp.maximum(above, below)
    return (d * d).sum(-1)


@jax.jit
def lb_keogh_batch_jnp(xs: jax.Array, U: jax.Array, L: jax.Array) -> jax.Array:
    """LB_Keogh of every candidate against every query envelope:
    ``xs [m, n]``, ``U/L [Q, n]`` → ``[Q, m]`` (sqrt of the squared core)."""
    return jnp.sqrt(lb_keogh2_batch_jnp(xs, U, L))


def _window_max(x: jax.Array, r: int) -> jax.Array:
    """Sliding-window max over the last axis (window ``[i-r, i+r]``,
    edge-clamped) via van Herk/Gil–Werman: block prefix/suffix running
    maxes at block width ``2r+1``, then one max of two gathers — ~5 passes
    over the data whatever the band, where a naive ``reduce_window``
    lowers to ``2r+1`` passes on CPU.  Exact (not an approximation)."""
    n = x.shape[-1]
    if r <= 0:
        return x
    w = 2 * r + 1
    nb = -(-(n + r) // w)           # blocks must cover index n-1+r
    pad = jnp.full(x.shape[:-1] + (nb * w - n,), -jnp.inf, x.dtype)
    blocks = jnp.concatenate([x, pad], axis=-1) \
        .reshape(x.shape[:-1] + (nb, w))
    ax = blocks.ndim - 1                  # cummax rejects negative axes
    run = jax.lax.cummax(blocks, axis=ax) \
        .reshape(x.shape[:-1] + (nb * w,))                 # prefix per block
    suf = jnp.flip(jax.lax.cummax(jnp.flip(blocks, -1), axis=ax), -1) \
        .reshape(x.shape[:-1] + (nb * w,))                 # suffix per block
    lead = jnp.full(x.shape[:-1] + (r,), -jnp.inf, x.dtype)
    s_l = jnp.concatenate([lead, suf], axis=-1)[..., :n]   # suf[i - r]
    r_e = run[..., r:r + n]                                # run[i + r]
    return jnp.maximum(s_l, r_e)


def _window_min(x: jax.Array, r: int) -> jax.Array:
    """Sliding-window min over the last axis (same contract as
    :func:`_window_max`)."""
    return -_window_max(-x, r)


def lb_improved2_batch_jnp(xs: jax.Array, qs: jax.Array, U: jax.Array,
                           L: jax.Array, r: int) -> jax.Array:
    """Squared LB_Improved (Lemire 2009): the two-pass envelope bound
    ``LB_Keogh(x, env(q))² + LB_Keogh(q, env(h))²`` with ``h = clip(x, L, U)``
    the projection of the candidate onto the query envelope.

    ``xs [m, n]`` (shared block) or ``[Q, m, n]`` (per-query gather layout),
    ``qs [Q, n]``, ``U/L [Q, n]`` → ``[Q, m]`` squared bounds.  Dominates
    LB_Keogh (the first term *is* LB_Keogh and the second is ≥ 0) and still
    lower-bounds banded DTW² — both property-tested against ``dtw_np`` in
    ``tests/test_dtw_cascade.py``.  This is the second stage of the DTW
    candidate cascade (LB_Keogh → LB_Improved → band DP): the extra
    elementwise pass is far cheaper than the O(n·band) DP it spares."""
    xsb = xs if xs.ndim == 3 else xs[None, :, :]
    above = jnp.maximum(xsb - U[:, None, :], 0.0)
    below = jnp.maximum(L[:, None, :] - xsb, 0.0)
    d1 = jnp.maximum(above, below)
    h = jnp.clip(xsb, L[:, None, :], U[:, None, :])
    Uh = _window_max(h, r)
    Lh = _window_min(h, r)
    d2 = jnp.maximum(jnp.maximum(qs[:, None, :] - Uh, 0.0),
                     jnp.maximum(Lh - qs[:, None, :], 0.0))
    return (d1 * d1).sum(-1) + (d2 * d2).sum(-1)


def _dtw2_masked_scan_full(q: jax.Array, xs: jax.Array, r: int,
                           mask: jax.Array, cutoff2: jax.Array) -> jax.Array:
    """Full-width anti-diagonal DP (frontier = all ``n`` columns) — the
    fallback of :func:`_dtw2_masked_scan` when the band covers the whole
    matrix (``r + 1 >= n``), where compaction buys nothing."""
    n = q.shape[0]
    m = xs.shape[0]
    INF = jnp.float32(jnp.inf)
    jidx = jnp.arange(n)
    zpad = jnp.zeros(n, q.dtype)
    qpad = jnp.concatenate([zpad, q, zpad])   # q[d - j] = qpad[n + d - j]

    def cond(carry):
        d, _, _, alive = carry
        return (d < 2 * n - 1) & alive.any()

    def body(carry):
        d, dm2, dm1, alive = carry
        i = d - jidx                                       # [n] row of col j
        inband = (i >= 0) & (i < n) & (jnp.abs(i - jidx) <= r)
        qd = jnp.flip(jax.lax.dynamic_slice(qpad, (d + 1,), (n,)))
        c = (xs - qd[None, :]) ** 2                        # [m, n] cost(i, j)
        left = jnp.concatenate([jnp.full((m, 1), INF), dm1[:, :-1]], axis=1)
        diag = jnp.concatenate([jnp.full((m, 1), INF), dm2[:, :-1]], axis=1)
        best = jnp.minimum(jnp.minimum(dm1, left), diag)
        best = jnp.where((d == 0) & (jidx == 0)[None, :], 0.0, best)
        out = jnp.where(inband[None, :], c + best, INF)
        lane_min = jnp.minimum(out.min(axis=1), dm1.min(axis=1))
        return d + 1, dm1, out, alive & (lane_min <= cutoff2)

    init = (jnp.int32(0), jnp.full((m, n), INF), jnp.full((m, n), INF),
            mask)
    _, _, dm1, alive = jax.lax.while_loop(cond, body, init)
    return jnp.where(alive, dm1[:, n - 1], INF)


def _dtw2_masked_scan(q: jax.Array, xs: jax.Array, r: int, mask: jax.Array,
                      cutoff2: jax.Array) -> jax.Array:
    """Anti-diagonal banded DTW² of one query vs a candidate block with lane
    masking and cutoff early-abandon: ``q [n]``, ``xs [m, n]``, ``mask [m]``,
    ``cutoff2`` scalar → squared distances ``[m]`` (masked/abandoned lanes
    come back ``+inf``).

    The DP walks the 2n-1 anti-diagonals (cells on diagonal ``d`` depend
    only on diagonals ``d-1``/``d-2``), so the sequential depth is O(n)
    instead of the row-scan's O(n²), and the carried frontier is
    *band-compacted* to the ``r+1`` in-band slots of each diagonal
    (slot ``o`` of diagonal ``d`` is column ``j = base(d) + o`` with
    ``base(d) = clip(⌈(d-r)/2⌉, 0, n-1-r)``) — each step is one vectorized
    ``[m, r+1]`` update instead of ``[m, n]``, an ``n/(r+1)``-fold work cut
    at the usual 10% band.  The ``while_loop`` exits as soon as every lane
    is dead: a lane dies when its LB_Keogh mask is off, or when the min DP
    value over its last two diagonals exceeds ``cutoff2`` (every warping
    path crosses a cell of diagonal ``d`` or ``d-1``, and path values only
    grow, so the final distance is bounded below by that min).  This is how
    LB-masked candidates *skip* DP work rather than paying it under a
    where-mask."""
    n = q.shape[0]
    if r + 1 >= n:
        return _dtw2_masked_scan_full(q, xs, r, mask, cutoff2)
    m = xs.shape[0]
    Wb = r + 1
    INF = jnp.float32(jnp.inf)
    oidx = jnp.arange(Wb)
    zpad = jnp.zeros(n, q.dtype)
    qpad = jnp.concatenate([zpad, q, zpad])   # q[i] = qpad[n + i]

    def base(d):
        return jnp.clip((d - r + 1) // 2, 0, n - 1 - r)

    def cond(carry):
        d, _, _, alive = carry
        return (d < 2 * n - 1) & alive.any()

    def body(carry):
        d, dm2, dm1, alive = carry
        b = base(d)
        s1 = b - base(d - 1)                    # slot shift vs diagonal d-1
        s2 = b - base(d - 2)                    # slot shift vs diagonal d-2
        j = b + oidx                                        # [Wb] columns
        i = d - j                                           # [Wb] rows
        valid = (i >= 0) & (i < n) & (j < n) & (jnp.abs(i - j) <= r)
        xwin = jax.lax.dynamic_slice(xs, (0, b), (m, Wb))
        qd = jnp.flip(jax.lax.dynamic_slice(
            qpad, (n + d - b - Wb + 1,), (Wb,)))            # q[d - j]
        c = (xwin - qd[None, :]) ** 2                       # [m, Wb]
        pad1 = jnp.full((m, 1), INF)
        up = jax.lax.dynamic_slice(                         # dm1[o + s1]
            jnp.concatenate([dm1, pad1], 1), (0, s1), (m, Wb))
        left = jax.lax.dynamic_slice(                       # dm1[o + s1 - 1]
            jnp.concatenate([pad1, dm1, pad1], 1), (0, s1), (m, Wb))
        diag = jax.lax.dynamic_slice(                       # dm2[o + s2 - 1]
            jnp.concatenate([pad1, dm2, pad1, pad1], 1), (0, s2), (m, Wb))
        best = jnp.minimum(jnp.minimum(up, left), diag)
        best = jnp.where((d == 0) & (j == 0)[None, :], 0.0, best)
        out = jnp.where(valid[None, :], c + best, INF)
        lane_min = jnp.minimum(out.min(axis=1), dm1.min(axis=1))
        return d + 1, dm1, out, alive & (lane_min <= cutoff2)

    init = (jnp.int32(0), jnp.full((m, Wb), INF), jnp.full((m, Wb), INF),
            mask)
    _, _, dm1, alive = jax.lax.while_loop(cond, body, init)
    # final cell (n-1, n-1) sits at slot (n-1) - base(2n-2) of diag 2n-2
    slot = (n - 1) - int(np.clip((2 * n - 2 - r + 1) // 2, 0, n - 1 - r))
    return jnp.where(alive, dm1[:, slot], INF)


@functools.partial(jax.jit, static_argnums=(2,))
def dtw2_masked_batch_jnp(qs: jax.Array, xs: jax.Array, r: int,
                          mask: jax.Array, cutoff2: jax.Array) -> jax.Array:
    """Masked banded DTW² of a query batch vs a shared candidate block:
    ``qs [Q, n]``, ``xs [m, n]``, ``mask [Q, m]``, ``cutoff2 [Q]`` →
    ``[Q, m]`` squared distances (``+inf`` for masked/abandoned lanes).
    The fused-DP core of the device DTW search paths (``ops.dtw_band``
    routes here off-TPU)."""
    return jax.vmap(
        lambda q, mk, ct: _dtw2_masked_scan(q, xs, r, mk, ct)
    )(qs, mask, cutoff2)


@functools.partial(jax.jit, static_argnums=(2,))
def dtw2_masked_gather_jnp(qs: jax.Array, cand: jax.Array, r: int,
                           mask: jax.Array, cutoff2: jax.Array) -> jax.Array:
    """Masked banded DTW² with *per-query* candidate sets (the leaf-gather
    layout of the approximate/extended scans): ``qs [Q, n]``,
    ``cand [Q, m, n]``, ``mask [Q, m]``, ``cutoff2 [Q]`` → ``[Q, m]``."""
    return jax.vmap(
        lambda q, c, mk, ct: _dtw2_masked_scan(q, c, r, mk, ct)
    )(qs, cand, mask, cutoff2)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def dtw_topk_masked_jnp(qs: jax.Array, xs: jax.Array, r: int, k: int,
                        block: int = 256) -> tuple[jax.Array, jax.Array]:
    """Exact banded-DTW top-k where LB_Keogh-masked candidates *skip* the
    DP: ``qs [Q, n]``, ``xs [m, n]`` → ``(d [Q, kk], ids [Q, kk])``,
    ``kk = min(k, m)`` — the fused replacement of the full-DP scan in
    :func:`dtw_topk_batch_jnp` (same contract, same exactness).

    Structure mirrors the ED span-schedule loop: candidates sort by their
    min-over-queries LB_Keogh into fixed ``block`` slabs, a per-query
    suffix-min over block LBs drives ``while_loop`` early termination, and
    inside a block only candidates with ``LB² < τ²`` (τ = the running k-th
    best, threaded through the scan) run the anti-diagonal DP — every true
    top-k member has ``LB ≤ d < τ``, so the returned distances are exact."""
    Q, n = qs.shape
    m = xs.shape[0]
    kk = min(k, m)
    U, L = dtw_envelope_batch_jnp(qs, r)
    lbk2 = lb_keogh2_batch_jnp(xs, U, L)                    # [Q, m]
    order = jnp.argsort(lbk2.min(axis=0))
    mp = -(-m // block) * block
    pad = mp - m
    xs_s = jnp.concatenate([xs[order], jnp.zeros((pad, n), xs.dtype)])
    ids_s = jnp.concatenate(
        [order.astype(jnp.int32), jnp.full(pad, -1, jnp.int32)])
    lbk2_s = jnp.concatenate(
        [lbk2[:, order], jnp.full((Q, pad), jnp.inf, jnp.float32)], axis=1)
    W = mp // block
    blk_lb = lbk2_s.reshape(Q, W, block).min(axis=2)        # [Q, W]
    suffix = jnp.flip(jax.lax.cummin(jnp.flip(blk_lb, 1), axis=1), 1)
    suffix = jnp.concatenate(
        [suffix, jnp.full((Q, 1), jnp.inf, jnp.float32)], axis=1)

    def cond(carry):
        i, topd, _ = carry
        return (i < W) & jnp.any(suffix[:, i] < topd[:, kk - 1])

    def body(carry):
        i, topd, topi = carry
        slab = jax.lax.dynamic_slice(xs_s, (i * block, 0), (block, n))
        sid = jax.lax.dynamic_slice(ids_s, (i * block,), (block,))
        lb_blk = jax.lax.dynamic_slice(lbk2_s, (0, i * block), (Q, block))
        cutoff = topd[:, kk - 1]
        msk = (lb_blk < cutoff[:, None]) & (sid >= 0)[None, :]
        d2 = dtw2_masked_batch_jnp(qs, slab, r, msk, cutoff)
        idt = jnp.where(jnp.isinf(d2), -1,
                        jnp.broadcast_to(sid[None, :], (Q, block)))
        alld = jnp.concatenate([topd, d2], axis=1)
        alli = jnp.concatenate([topi, idt], axis=1)
        neg, sel = jax.lax.top_k(-alld, kk)
        return i + 1, -neg, jnp.take_along_axis(alli, sel, axis=1)

    init = (jnp.int32(0), jnp.full((Q, kk), jnp.inf, jnp.float32),
            jnp.full((Q, kk), -1, jnp.int32))
    _, topd, topi = jax.lax.while_loop(cond, body, init)
    return jnp.sqrt(topd), topi


@functools.partial(jax.jit, static_argnums=(2, 3))
def dtw_topk_batch_jnp(qs: jax.Array, xs: jax.Array, r: int, k: int
                       ) -> tuple[jax.Array, jax.Array]:
    """Exact banded-DTW top-k for a query batch with LB_Keogh pre-filtering:
    ``qs [Q, n]``, ``xs [m, n]`` → ``(d [Q, kk], ids [Q, kk])`` with
    ``kk = min(k, m)`` (fewer candidates than ``k`` narrows the result —
    callers that need a fixed ``k`` pad like the search paths do).

    Seeds the cutoff τ from exact DTW on the ``k`` best candidates by
    LB_Keogh, then only candidates with ``LB_Keogh < τ`` keep their exact
    distance in the candidate scan (every true top-k member has
    ``LB ≤ d < τ``, so the result distances are exact).  The mask is the
    pruning structure the fused TPU kernel consumes; under jnp it is a
    where-mask over the vmapped DP."""
    m = xs.shape[0]
    kk = min(k, m)
    U, L = dtw_envelope_batch_jnp(qs, r)
    lbk = lb_keogh_batch_jnp(xs, U, L)                      # [Q, m]
    _, seed = jax.lax.top_k(-lbk, kk)                       # [Q, kk]
    seed_d = jax.vmap(lambda q, s: _dtw_scan(q, xs[s], r))(qs, seed)
    tau = seed_d.max(axis=1)                                # kth-best seed
    mask = lbk < tau[:, None]
    mask = jnp.zeros_like(mask).at[
        jnp.arange(qs.shape[0])[:, None], seed].set(True) | mask
    d = dtw_batch_queries_jnp(qs, xs, r, mask)
    neg, ids = jax.lax.top_k(-d, kk)
    return -neg, ids
