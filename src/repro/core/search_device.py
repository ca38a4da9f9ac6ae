"""Device-resident kNN over a :class:`~repro.core.device_index.DeviceIndex`
— the batched/sharded analogue of ``search.exact_search`` (DESIGN.md §2).

The host variant walks leaves in LB order and stops early (the disk-search
analogue).  Here the same plan is one XLA program per shard:

    lb        = MINDIST(PAA(q), every local leaf)      (lb_isax math)
    span LB   = segment-min over intersecting leaves
    order     = argsort(min-over-queries span LB)
    while any query still has an unpruned span:        (span_scan kernel)
        slab  = the next span's rows, HBM → VMEM       (fixed-size span)
        d     = |q - slab|²                            (MXU form, whole batch)
        topk  = merge(topk, d)                         (per-query active mask)

The walk is one ``ops.span_scan`` call over the leading shard axis of the
``DeviceIndex`` (a Pallas kernel on the TPU, an XLA ``while_loop`` off
it; DTW keeps the loop).  On a mesh it runs inside ``shard_map``, each
chip over its own shards, and GSPMD turns the final merge

    [S, Q, kk] --all-gather--> [Q, S·kk] --dedup+top_k--> [Q, kk]

into one collective.  Exactness carries over: each shard's early
termination uses its local kth-best bound (≥ the global bound), so every
shard's local top-kk is a superset of its contribution to the global top-kk.

Fuzzy-duplicate dedup happens inside the device merge (a segment-min over
original ids: lexsort each row by (id, d²), keep the first slot of every id
run, re-select top-k) — serving never leaves the device, and the results are
bitwise-identical whatever the shard count because the dedup output depends
only on the (id, d²) value set, not the concatenation order.

The exact path finishes with a tiny k-sized host re-rank: the loop ranks by
the MXU-friendly ``|q|²+|x|²-2qx`` form whose rounding can swap near-ties
relative to the host's direct-difference sum; recomputing the k candidates
with host math (and sorting by (d, id), the host heap's order) restores
bitwise id/distance parity with ``search.exact_search``.

Approximate search is batched by flattening the host routing tree into
arrays (held by the ``DeviceIndex``) so the root→leaf dict-walk becomes a
vectorized ``fori_loop`` descent over the whole query batch; its leaf scan
addresses the flattened ``[S·Tp, n]`` view of the shard layout.

Extended search (paper Alg. 4) reuses the same descent but stops at the
smallest subtree within the ``nbr`` leaf budget, builds a per-query visit
schedule from the sibling routing tables (target subtree first, remaining
siblings by lower bound, leaves by lower bound within each), and scans the
schedule shard-locally before the same all-gather dedup merge — see
``extended_search_device_batch``.

Every path is *metric-pluggable* (``core.metric``): the query preprocessing
produces a per-segment interval (ED: the PAA itself; DTW: the LB_Keogh
envelope summary) feeding one interval-MINDIST bound everywhere a region is
ranked, and the candidate distance is either the MXU ED form or the fused
masked banded-DTW DP (``ops.dtw_band``) behind the LB_Keogh → LB_Improved
cascade, with the running top-k cutoff threaded through the scan.  The
``Metric`` struct is a jit static argument, so the ED programs lower exactly
as before and DTW specializes separately.

The DTW exact path ("DTW fast path", docs/device_index.md):

- **one layout** — DTW shares the ED-width ``chunk`` layout; the span body
  sub-blocks each slab with a ``fori_loop`` over ``DTW_SUB``-wide sub-slabs
  (bounding the DP-frontier memory the old narrow ``DTW_CHUNK`` layout
  existed for) and re-reads the running cutoff between sub-blocks, so later
  sub-blocks inherit the pruning the earlier ones just earned;
- **cascade** — LB_Keogh, then LB_Improved (second-pass envelope of the
  LB_Keogh projection), then the band DP; each stage masks the next, so
  only cascade survivors pay O(n·band), and per-stage kill counters are
  threaded out for observability;
- **per-query ordering** (``Metric.order``) — instead of the shared
  min-over-queries span order, the ``"perq"``/``"cluster"`` program sorts
  every query's *lanes* by its own LB_Improved and walks gather-chunks of
  that personal best-first order (seeding the cutoff with a DP over the
  first ``kk`` candidates); ``"cluster"`` additionally groups queries by
  estimated surviving-lane count into sub-batches with independent
  while_loops so light queries stop idling behind stragglers.
"""
from __future__ import annotations

import functools
import itertools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .device_index import DeviceIndex, data_axes
from .index import DumpyIndex
from .lb import (dtw2_masked_gather_jnp, dtw_np_batch, ed2_batch_jnp,
                 lb_improved2_batch_jnp, lb_keogh2_batch_jnp)
from .metric import ED, Metric, default_band, query_prep_jnp, resolve
from .sax import sax_encode_jnp
from repro import obs
from repro.kernels import ops
from repro.robustness.failpoints import failpoint, with_retries

# DTW sub-block width inside a span slab: the anti-diagonal DP carries two
# [Q, sub, band+1] frontiers, so sub-blocking the ED-width slab keeps the
# DP state small (≈ 256·(band+1)·Q·4B·2 per sub-block) without a second,
# narrower DeviceIndex layout
DTW_SUB = 256
# gather-chunk width of the per-query lane-ordered programs
DTW_LANE_CHUNK = 128
# lane-chunk width of the LB_Improved table precompute (bounds the
# [Q, chunk, n] envelope temporaries)
DTW_LB_CHUNK = 2048
# vmap axis name of the per-shard span loops (see span_loop)
SHARD_AXIS = "shard"


# ---------------------------------------------------------------------------
# shared device helpers
# ---------------------------------------------------------------------------

def _encode_batch(qs: jax.Array, w: int, b: int) -> tuple[jax.Array, jax.Array]:
    if jax.default_backend() == "tpu":
        return ops.sax_encode(qs, w, b)
    return sax_encode_jnp(qs, w, b)


def _interval_lb(dev: DeviceIndex, seg_lo: jax.Array, seg_hi: jax.Array,
                 lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Squared interval MINDIST ``[Q, R]`` of the query batch to a replicated
    region table ``lo/hi [R, w]``.  On a mesh-placed index the kernel runs in
    a replicated ``shard_map`` — XLA cannot partition a Pallas kernel, so
    every chip computes the small table itself."""
    f = lambda a, b, c, d: ops.lb_paa_interval(a, b, c, d, dev.n)
    if dev.mesh is None:
        return f(seg_lo, seg_hi, lo, hi)
    # check_vma off: a pallas_call's output type carries no varying-axes
    # annotation for the checker to verify
    return jax.shard_map(f, mesh=dev.mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(seg_lo, seg_hi, lo, hi)


def _shard_local(dev: DeviceIndex, fn, *xs):
    """``fn`` over the ``[S, ...]`` shard arrays ``xs``.  On a mesh-placed
    index it runs inside ``shard_map``, so each chip sees only its own
    shards and the Pallas kernels in ``fn`` run chip-locally (a reduction
    over :data:`SHARD_AXIS` inside ``fn`` then spans the chip's own
    shards)."""
    if dev.mesh is None:
        return fn(*xs)
    spec = P(data_axes(dev.mesh))
    return jax.shard_map(fn, mesh=dev.mesh, in_specs=spec,
                         out_specs=spec, check_vma=False)(*xs)


def _prep_batch(metric: Metric, qs_dev: jax.Array, w: int, b: int
                ) -> tuple[tuple, jax.Array]:
    """Encode + metric-preprocess a query batch → ``(prep, sax_q)`` with
    ``prep = (seg_lo, seg_hi, env_lo, env_hi)`` (see ``core.metric``)."""
    paa_q, sax_q = _encode_batch(qs_dev, w, b)
    return query_prep_jnp(metric, qs_dev, paa_q), sax_q.astype(jnp.int32)


#: slots of the per-stage cascade counter vector (i32[4]) the DTW programs
#: thread through their loops; ``dp_survivors = considered - killed_lb_keogh
#: - killed_lb_improved - dp_abandoned`` is derived at the end.
STAT_KEYS = ("considered", "killed_lb_keogh", "killed_lb_improved",
             "dp_abandoned")

#: slots of the span walk's work vector (i32[5]), recorded per exact call
#: as ``repro.obs`` counters of these names
WORK_KEYS = ("exact.spans_walked", "exact.rows_live", "exact.pairs_needed",
             "exact.spans_merged", "exact.merge_rounds")

_exact_calls = itertools.count()


def _cascade_stats(valid: jax.Array, lbk2: jax.Array, lbi2: jax.Array,
                   d2: jax.Array, cutoff2: jax.Array) -> jax.Array:
    """Per-stage kill counters of one cascade invocation → i32[4]
    (:data:`STAT_KEYS` order).  ``valid`` are the lanes the cascade looked
    at; a lane that ran the DP but came back ``+inf`` was cutoff-abandoned
    mid-DP."""
    ct = cutoff2[:, None]
    k1 = valid & (lbk2 >= ct)
    k2 = valid & (lbk2 < ct) & (lbi2 >= ct)
    ran = valid & (lbi2 < ct)
    ab = ran & jnp.isinf(d2)
    return jnp.stack([valid.sum(), k1.sum(), k2.sum(), ab.sum()]) \
        .astype(jnp.int32)


def _dist2_slab(metric: Metric, qs: jax.Array, prep: tuple, slab: jax.Array,
                valid: jax.Array, cutoff2: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """Squared candidate distances of the whole query batch against a shared
    candidate slab, with invalid/pruned entries as ``+inf``.  Returns
    ``(d2 [Q, m], stats i32[4])`` (stats all-zero for ED, where XLA
    dead-code-eliminates them).

    ``valid [Q, m]`` marks live candidates; ``cutoff2 [Q]`` is the running
    squared k-th best.  ED pays the MXU form for every candidate (the span
    loop already pruned at span granularity); DTW runs the lower-bound
    cascade — LB_Keogh, then the strictly tighter LB_Improved — and only
    lanes both stages leave below the cutoff pay the fused masked band
    DP."""
    if not metric.is_dtw:
        d2 = ed2_batch_jnp(qs, slab)
        return jnp.where(valid, d2, jnp.inf), jnp.zeros(4, jnp.int32)
    _, _, env_lo, env_hi = prep
    lbk2 = lb_keogh2_batch_jnp(slab, env_hi, env_lo)          # [Q, m]
    lbi2 = lb_improved2_batch_jnp(slab, qs, env_hi, env_lo, metric.band)
    mask = valid & (lbk2 < cutoff2[:, None]) & (lbi2 < cutoff2[:, None])
    d2 = ops.dtw_band(qs, slab, mask, cutoff2, metric.band)
    return d2, _cascade_stats(valid, lbk2, lbi2, d2, cutoff2)


def _dist2_gather(metric: Metric, qs: jax.Array, prep: tuple,
                  cand: jax.Array, valid: jax.Array, cutoff2: jax.Array
                  ) -> jax.Array:
    """As :func:`_dist2_slab` but with *per-query* candidate sets
    ``cand [Q, m, n]`` (the leaf-gather layout of the approximate/extended
    scans); returns just ``d2`` — the gather callers don't thread
    counters.  Masking a lane whose LB reaches the cutoff never changes a
    merge result (it could not displace any held slot), so the extra
    LB_Improved stage is result-invariant here too."""
    if not metric.is_dtw:
        d2 = ((cand - qs[:, None, :]) ** 2).sum(-1)
        return jnp.where(valid, d2, jnp.inf)
    _, _, env_lo, env_hi = prep
    lbk2 = lb_keogh2_batch_jnp(cand, env_hi, env_lo)
    lbi2 = lb_improved2_batch_jnp(cand, qs, env_hi, env_lo, metric.band)
    mask = valid & (lbk2 < cutoff2[:, None]) & (lbi2 < cutoff2[:, None])
    return dtw2_masked_gather_jnp(qs, cand, metric.band, mask, cutoff2)


def _validate_queries_struct(qs, n: int) -> np.ndarray:
    """Structural half of :func:`_validate_queries` — dtype/shape/length,
    everything except the O(Q·n) finite scan.  The serving front-end runs
    this per request at submit time and defers the finite scan to one
    vectorized pass per coalesced bucket (:func:`lane_finite_mask`), so
    validation cost is per-batch, not per-request, on the hot path."""
    qs = np.asarray(qs)
    if qs.dtype.kind not in "fiu":
        raise TypeError(
            f"queries must be real-numeric, got dtype {qs.dtype}")
    qs = np.atleast_2d(qs)
    if qs.ndim != 2:
        raise ValueError(
            f"queries must be [Q, n] (or [n]), got shape {qs.shape}")
    if qs.shape[1] != n:
        raise ValueError(
            f"query length {qs.shape[1]} != indexed series length {n}")
    return np.ascontiguousarray(qs, np.float32)


def lane_finite_mask(qs: np.ndarray) -> np.ndarray:
    """Vectorized NaN/Inf check over a coalesced batch: one ``np.isfinite``
    pass, ``True`` where the lane is bad.  Callers that must attribute the
    failure to the offending request raise :func:`lane_finite_error` for
    each bad lane, rather than the batched message ``_validate_queries``
    produces."""
    return ~np.isfinite(qs).all(axis=1)


def lane_finite_error() -> ValueError:
    """The exact exception ``_validate_queries`` raises for a bad batch of
    one — what the offending request would have seen had it been issued
    individually rather than coalesced."""
    return ValueError("queries [0] contain NaN/Inf values")


def _validate_queries(qs, n: int) -> np.ndarray:
    """Host-boundary query validation: a NaN/Inf query would silently poison
    every distance it touches (NaN compares false against any cutoff, so the
    top-k fills with garbage), and a wrong-length batch would either crash
    deep inside a jitted program or broadcast into nonsense.  Returns the
    batch as contiguous ``[Q, n] float32``."""
    qs = _validate_queries_struct(qs, n)
    bad = np.where(lane_finite_mask(qs))[0]
    if bad.size:
        raise ValueError(
            f"queries {bad[:8].tolist()} contain NaN/Inf values")
    return qs


def _mask_dead_shards(health, topd: jax.Array, topi: jax.Array,
                      vis: jax.Array | None = None,
                      st: jax.Array | None = None):
    """Degraded mode: erase dead shards' per-shard locals (``[S, Q, k]``)
    before the all-gather merge — their slots become ``+inf / -1``, which
    the dedup top-k treats as absent.  ``health`` is the static
    ``DeviceIndex.shard_health`` tuple; ``None`` (all healthy) is the
    identity, so healthy programs lower unchanged."""
    if health is None:
        return topd, topi, vis, st
    m = jnp.asarray(health, bool)                       # [S] constant
    topd = jnp.where(m[:, None, None], topd, jnp.inf)
    topi = jnp.where(m[:, None, None], topi, -1)
    if vis is not None:
        vis = jnp.where(m[:, None], vis, 0)
    if st is not None:
        st = jnp.where(m[:, None], st, 0)
    return topd, topi, vis, st


def shard_coverage(index: DumpyIndex, dev: DeviceIndex) -> float:
    """Fraction of distinct *live* series reachable through the surviving
    shards (1.0 when every shard is healthy).  Data-weighted, not
    shard-counted: fuzzy replication can make a series reachable from a
    surviving shard even when its first replica's shard is dead, and shards
    are leaf-aligned rather than perfectly equal-sized."""
    if dev.shard_health is None:
        return 1.0
    order = np.asarray(index.flat.order)
    alive = np.asarray(index.alive, bool)
    reach = np.zeros(alive.shape[0], bool)
    rb = dev.row_bounds
    for s, healthy in enumerate(dev.shard_health):
        if healthy:
            reach[order[rb[s]:rb[s + 1]]] = True
    total = int(alive.sum())
    if total == 0:
        return 1.0
    return float((reach & alive).sum()) / total


def _result_margin(dev: DeviceIndex, k: int) -> int:
    """Top-k width the device loop must carry: fuzzy duplication can fill up
    to ``1 + max_replica`` slots per distinct id (the plain layout needs no
    margin — a wider k weakens early termination for nothing)."""
    if dev.has_duplicates:
        return k * (1 + dev.max_replica)
    return k


def _dedup_topk(d2: jax.Array, ids: jax.Array, k: int
                ) -> tuple[jax.Array, jax.Array]:
    """Device dedup + final top-k: segment-min over original ids.

    Each row is lexsorted by (id, d²); the first slot of an id run is that
    id's min distance, later slots (fuzzy replicas) and ``-1`` sentinels are
    masked to ``+inf``; ``top_k`` then re-sorts by distance.  Ties between
    distinct ids resolve to the smaller id (the array is id-sorted), which
    matches the host heap's (d, id) order.  The output depends only on the
    (id, d²) value set — concatenation order (and hence shard count) cannot
    change it."""
    Q, C = ids.shape
    perm = jnp.lexsort((d2, ids), axis=-1)
    ids_s = jnp.take_along_axis(ids, perm, 1)
    d_s = jnp.take_along_axis(d2, perm, 1)
    first = jnp.concatenate(
        [jnp.ones((Q, 1), bool), ids_s[:, 1:] != ids_s[:, :-1]], axis=1)
    keep = first & (ids_s >= 0)
    d_m = jnp.where(keep, d_s, jnp.inf)
    i_m = jnp.where(keep, ids_s, -1)
    neg, sel = jax.lax.top_k(-d_m, min(k, C))
    return -neg, jnp.take_along_axis(i_m, sel, 1)


# ---------------------------------------------------------------------------
# sharded exact search (one XLA program; S=1 is the single-device case)
# ---------------------------------------------------------------------------

def _walk_schedule(lb_g: jax.Array, gid_s: jax.Array, w_start: jax.Array,
                   w_lead: jax.Array, w_size: jax.Array, e_leaf: jax.Array,
                   e_win: jax.Array) -> tuple[jax.Array, ...]:
    """One shard's walk: from the global leaf bounds ``lb_g [Q, L]`` and
    the shard's leaf ids and span schedule, the schedule in walk order
    ``(start, lead, size [W], win_lb [Q, W], suffix [Q, W+1])``: the span
    bounds, and their suffix minima with a ``+inf`` column appended."""
    Q = lb_g.shape[0]
    W = w_start.shape[0]
    # this shard's leaves (pad leaf: +inf) in local order
    lbq = jnp.where(gid_s[None, :] >= 0,
                    jnp.take(lb_g, jnp.maximum(gid_s, 0), axis=1),
                    jnp.inf)                                       # [Q, Lp]
    # span LB = min over intersecting leaves (exact: it lower-bounds every
    # series the span contains; pad edges hit the +inf pad leaf)
    win_lb = jax.ops.segment_min(lbq[:, e_leaf].T, e_win, num_segments=W,
                                 indices_are_sorted=True).T      # [Q, W]
    order = jnp.argsort(win_lb.min(axis=0))       # most promising for anyone
    win_lb = win_lb[:, order]
    suffix = jnp.flip(jax.lax.cummin(jnp.flip(win_lb, 1), axis=1), 1)
    suffix = jnp.concatenate(
        [suffix, jnp.full((Q, 1), jnp.inf, jnp.float32)], axis=1)
    return w_start[order], w_lead[order], w_size[order], win_lb, suffix


def span_loop(metric: Metric, qs: jax.Array, prep: tuple, db_s: jax.Array,
              alive_s: jax.Array, ids_s: jax.Array, w_start: jax.Array,
              w_lead: jax.Array, w_size: jax.Array, win_lb: jax.Array,
              suffix: jax.Array, *, k: int, chunk: int
              ) -> tuple[jax.Array, ...]:
    """One shard's span walk as an XLA ``while_loop``, vmapped over
    :data:`SHARD_AXIS`: the DTW walk, and off the TPU the jnp twin of the
    span-scan kernel (``ops.span_scan``).

    ``w_start/w_lead/w_size [W]`` is the walk-ordered schedule, ``win_lb
    [Q, W]`` the span bounds in that order and ``suffix [Q, W+1]`` their
    suffix minima with a ``+inf`` column appended.  Returns ``(topd [Q, k],
    topi [Q, k], vis [Q], cascade stats i32[4], work i32[5])``, ``work`` in
    :data:`WORK_KEYS` order; only the last three are carried.

    Each span (each DTW sub-slab) merges through
    :func:`ops.topk_merge_cutoff`.  DTW sub-blocks the slab with an inner
    ``fori_loop`` over ``DTW_SUB``-wide sub-slabs, which bounds the
    DP-frontier memory without a second narrow ``DeviceIndex``, and
    re-reads the running cutoff between sub-blocks, so each sub-slab prunes
    against everything earlier sub-slabs just merged."""
    Q = qs.shape[0]
    W = w_start.shape[0]
    n = db_s.shape[1]
    # sub-blocking needs exact tiling; a chunk that is no multiple of
    # DTW_SUB (or one already at/below it) runs the slab whole
    n_sub = chunk // DTW_SUB if (
        metric.is_dtw and chunk > DTW_SUB and chunk % DTW_SUB == 0) else 1
    sub_w = chunk // n_sub

    def cond(carry):
        i, topd = carry[0], carry[1]
        return (i < W) & jnp.any(suffix[:, i] < topd[:, k - 1])

    def body(carry):
        i, topd, topi, vis, st, pairs, merged, rounds = carry
        start = w_start[i]
        qact = win_lb[:, i] < topd[:, k - 1]                # [Q] active mask

        def sub(b, c2):
            topd, topi, st, hit, rounds = c2
            s0 = start + b * sub_w
            # pin the literal column index to int32: under an x64 env a
            # bare 0 defaults to int64 and dynamic_slice rejects the mixed
            # index dtypes (audit injection tests lower with x64)
            slab = jax.lax.dynamic_slice(db_s, (s0, jnp.int32(0)),
                                         (sub_w, n))
            j = b * sub_w + jnp.arange(sub_w)               # slab-local rows
            valid = (j >= w_lead[i]) & (j < w_lead[i] + w_size[i])
            valid &= jax.lax.dynamic_slice(alive_s, (s0,), (sub_w,))
            # cutoff re-read each sub-block: later sub-slabs prune against
            # what earlier ones merged
            qact_b = qact & (win_lb[:, i] < topd[:, k - 1])
            d2, stt = _dist2_slab(metric, qs, prep, slab,
                                  valid[None, :] & qact_b[:, None],
                                  topd[:, k - 1])
            sid = jax.lax.dynamic_slice(ids_s, (s0,), (sub_w,))
            topd, topi, m = ops.topk_merge_cutoff(topd, topi, d2, sid,
                                                  SHARD_AXIS)
            return topd, topi, st + stt, hit | (m > 0), rounds + m

        c2 = (topd, topi, st, jnp.bool_(False), rounds)
        if n_sub == 1:
            topd, topi, st, hit, rounds = sub(0, c2)
        else:
            topd, topi, st, hit, rounds = jax.lax.fori_loop(0, n_sub, sub, c2)
        act = qact.astype(jnp.int32)
        pairs = pairs + w_size[i] * act.sum(dtype=jnp.int32)
        return (i + 1, topd, topi, vis + act, st, pairs,
                merged + hit.astype(jnp.int32), rounds)

    init = (jnp.int32(0),
            jnp.full((Q, k), jnp.inf, jnp.float32),
            jnp.full((Q, k), -1, jnp.int32),
            jnp.zeros((Q,), jnp.int32),
            jnp.zeros(4, jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0))
    i, topd, topi, vis, st, pairs, merged, rounds = jax.lax.while_loop(
        cond, body, init)
    # the walk is a prefix of the order: its rows need no carry
    rows = jnp.where(jnp.arange(W) < i, w_size, 0).sum(dtype=jnp.int32)
    return topd, topi, vis, st, jnp.stack([i, rows, pairs, merged, rounds])


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _exact_knn_sharded(dev: DeviceIndex, prep: tuple, qs: jax.Array, *,
                       k: int, metric: Metric = ED
                       ) -> tuple[jax.Array, ...]:
    """Interval-MINDIST tables → per-shard span walks → all-gather merge
    with in-merge dedup.  Returns ``(d [Q,k], original ids [Q,k],
    spans_visited [Q], cascade stats i32[4], work i32[5])`` with invalid
    slots as ``inf / -1`` (stats are all-zero for ED).  ``work`` counts the
    walk in :data:`WORK_KEYS` order, summed over shards (dead ones
    included: their walks still run): spans walked, their live rows
    (``w_size``), live rows × queries active at the span, spans at which
    some candidate entered a query's running top-k, and the merge's
    insertion rounds (per span the most candidates any query brings in,
    capped at ``k``).

    Per shard a vmapped prelude builds the span bounds (the metric's
    interval MINDIST, min over each span's leaves), the walk order shared
    by the batch (ascending min-over-queries bound) and the suffix minima
    of the bounds along it.  The walk itself depends on the metric and the
    backend:

    - **ED on a TPU** (series length a multiple of 128): one
      ``ops.span_scan`` Pallas kernel for all the chip's shards (grid
      shards × spans), which streams each span's slab from HBM once and
      keeps the running top-k in VMEM (docs/device_index.md, "Span
      scan").  This covers the benchmark's exact path,
      ``exact_search_device``, degraded mode and the sharded search on a
      mesh.
    - **ED otherwise** (off the TPU, or another length): the same call
      runs :func:`span_loop`, the kernel's jnp twin.
    - **DTW** (``order="shared"``): :func:`span_loop` with the LB cascade
      and the band DP as the span body, on every backend.

    Early termination is per query *and* per shard: along the shard's span
    order, query q may stop merging at step i iff its suffix-min LB there is
    ≥ its running kth best — every span it has not seen locally is
    individually prunable — and the walk stops when no query may go on."""
    Q = qs.shape[0]
    seg_lo, seg_hi = prep[0], prep[1]

    # one kernel call on the replicated global leaf table; each shard
    # gathers its own leaves' bounds from it
    lb_g = _interval_lb(dev, seg_lo, seg_hi, dev.leaf_lo_g,
                        dev.leaf_hi_g)                             # [Q, L] sq

    def walk(db, alive, ids, *sched):
        start, lead, size, win_lb, suffix = jax.vmap(
            functools.partial(_walk_schedule, lb_g))(*sched)
        if metric.is_dtw:
            return jax.vmap(
                functools.partial(span_loop, metric, qs, prep, k=k,
                                  chunk=dev.chunk),
                axis_name=SHARD_AXIS)(db, alive, ids, start, lead, size,
                                      win_lb, suffix)
        topd, topi, vis, work = ops.span_scan(
            qs, db, alive, ids, start, lead, size, win_lb, suffix, kk=k,
            chunk=dev.chunk)
        return topd, topi, vis, jnp.zeros((db.shape[0], 4), jnp.int32), work

    topd, topi, vis, st, work = _shard_local(
        dev, walk, dev.db, dev.alive, dev.ids, dev.leaf_gid,
        dev.win_start, dev.win_lead, dev.win_size,
        dev.edge_leaf, dev.edge_win)                        # [S, Q, k]
    topd, topi, vis, st = _mask_dead_shards(dev.shard_health,
                                            topd, topi, vis, st)
    S = topd.shape[0]
    alld = jnp.moveaxis(topd, 0, 1).reshape(Q, S * k)       # all-gather when
    alli = jnp.moveaxis(topi, 0, 1).reshape(Q, S * k)       # sharded over S
    d2m, idm = _dedup_topk(alld, alli, k)
    return (jnp.sqrt(d2m), idm, vis.sum(axis=0), st.sum(axis=0),
            work.sum(axis=0))


def _cluster_groups(Q: int) -> int:
    """Static sub-batch count of the ``"cluster"`` ordering: enough groups
    that stragglers stop holding the whole batch, few enough that each
    group's while_loop still amortizes its gather dispatches."""
    if Q % 4 == 0 and Q >= 32:
        return 4
    if Q % 2 == 0 and Q >= 16:
        return 2
    return 1


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _exact_knn_lane_sharded(dev: DeviceIndex, prep: tuple, qs: jax.Array, *,
                            k: int, metric: Metric
                            ) -> tuple[jax.Array, ...]:
    """The per-query-ordered DTW exact program (``Metric.order`` ∈
    {"perq", "cluster"}): same contract as :func:`_exact_knn_sharded`
    (``vis`` counts gather-chunks a query was live for, the analogue of
    spans visited), except that it walks no span schedule and so counts
    no loop work: its ``work`` is ``None``.

    Per shard: (1) a lane-chunked precompute builds the full LB_Keogh and
    LB_Improved tables ``[Q, Tp]``; (2) every query argsorts *its own* lanes
    by LB_Improved; (3) a DP over each query's first ``k`` lanes seeds the
    running top-k — the best cutoff any k candidates can buy; (4) a
    while_loop walks ``DTW_LANE_CHUNK``-wide gather-chunks of the sorted
    ranks, pruning each chunk against the re-read cutoff, until the
    smallest remaining LB of every query reaches its cutoff.  Because each
    query's lanes arrive ascending by LB, the suffix condition is just the
    chunk's first column, and the visited prefix is exactly the candidate
    superset the host proof needs: every unvisited lane has
    ``LB_Improved ≥ cutoff ≥ final k-th best ≤ DTW``.

    ``"cluster"`` additionally argsorts queries by estimated surviving-lane
    count (``#{lanes: LB_Improved < seed cutoff}``) and runs one while_loop
    per contiguous sub-batch: a query's own merge sequence is unchanged
    (extra iterations of a shared loop merge nothing once it is inactive),
    so the results are bitwise those of ``"perq"`` — only the wasted
    gather dispatches of light queries go away."""
    Q, n = qs.shape
    r = metric.band
    _, _, env_lo, env_hi = prep
    G = _cluster_groups(Q) if metric.order == "cluster" else 1

    def per_shard(db_s, alive_s, ids_s):
        Tp = db_s.shape[0]
        if Tp == 0:                                          # empty shard
            return (jnp.full((Q, k), jnp.inf, jnp.float32),
                    jnp.full((Q, k), -1, jnp.int32),
                    jnp.zeros((Q,), jnp.int32), jnp.zeros(4, jnp.int32))
        C = min(DTW_LANE_CHUNK, Tp)
        LC = min(DTW_LB_CHUNK, Tp)
        kseed = min(k, Tp)

        # ---- stage 1: LB tables over every lane (chunked precompute) ----
        def lb_chunk(c, tabs):
            lbk_t, lbi_t = tabs
            s0 = jnp.minimum(c * LC, Tp - LC)   # tail chunk recomputes a few
            slab = jax.lax.dynamic_slice(db_s, (s0, 0), (LC, n))
            al = jax.lax.dynamic_slice(alive_s, (s0,), (LC,))
            lbk2 = lb_keogh2_batch_jnp(slab, env_hi, env_lo)
            lbi2 = lb_improved2_batch_jnp(slab, qs, env_hi, env_lo, r)
            lbk2 = jnp.where(al[None, :], lbk2, jnp.inf)
            lbi2 = jnp.where(al[None, :], lbi2, jnp.inf)
            return (jax.lax.dynamic_update_slice(lbk_t, lbk2, (0, s0)),
                    jax.lax.dynamic_update_slice(lbi_t, lbi2, (0, s0)))

        init_t = (jnp.zeros((Q, Tp), jnp.float32),
                  jnp.zeros((Q, Tp), jnp.float32))
        lbk_all, lbi_all = jax.lax.fori_loop(0, -(-Tp // LC), lb_chunk,
                                             init_t)

        # ---- stage 2: per-query lane order, ascending LB_Improved ----
        order = jnp.argsort(lbi_all, axis=1)                 # [Q, Tp]
        lbi_s = jnp.take_along_axis(lbi_all, order, 1)
        lbk_s = jnp.take_along_axis(lbk_all, order, 1)

        # ---- stage 3: seed DP over each query's k best-LB lanes ----
        seed_idx = order[:, :kseed]
        seed_ok = jnp.isfinite(lbi_s[:, :kseed])             # dead lanes: inf
        d2s = dtw2_masked_gather_jnp(qs, db_s[seed_idx], r, seed_ok,
                                     jnp.full((Q,), jnp.inf, jnp.float32))
        idt = jnp.where(jnp.isinf(d2s), -1, ids_s[seed_idx])
        topd, topi = ops.topk_merge(jnp.full((Q, k), jnp.inf, jnp.float32),
                                    jnp.full((Q, k), -1, jnp.int32),
                                    d2s, idt)
        st = jnp.stack([seed_ok.sum(), 0, 0,
                        (seed_ok & jnp.isinf(d2s)).sum()]).astype(jnp.int32)

        # ---- stage 4: gather-chunk walk of the sorted ranks ----
        NC = max(-(-(Tp - kseed) // C), 0)

        def walk(qs_g, order_g, lbi_g, lbk_g, topd_g, topi_g):
            Qg = qs_g.shape[0]

            def cond(carry):
                c, topd, topi, vis, st = carry
                r0 = jnp.minimum(kseed + c * C, Tp - 1)
                front = jax.lax.dynamic_slice(lbi_g, (0, r0), (Qg, 1))[:, 0]
                return (c < NC) & jnp.any(front < topd[:, k - 1])

            def body(carry):
                c, topd, topi, vis, st = carry
                r0 = kseed + c * C
                s = jnp.minimum(r0, Tp - C)
                fresh = jnp.arange(C) >= (r0 - s)   # ranks < r0 already seen
                idx = jax.lax.dynamic_slice(order_g, (0, s), (Qg, C))
                lbi_c = jax.lax.dynamic_slice(lbi_g, (0, s), (Qg, C))
                lbk_c = jax.lax.dynamic_slice(lbk_g, (0, s), (Qg, C))
                cutoff = topd[:, k - 1]
                seen = fresh[None, :] & jnp.isfinite(lbi_c)
                mask = seen & (lbi_c < cutoff[:, None])
                cand = db_s[idx]                             # [Qg, C, n]
                d2 = dtw2_masked_gather_jnp(qs_g, cand, r, mask, cutoff)
                idt = jnp.where(jnp.isinf(d2), -1, ids_s[idx])
                topd, topi = ops.topk_merge(topd, topi, d2, idt)
                st = st + _cascade_stats(seen, lbk_c, lbi_c, d2, cutoff)
                return (c + 1, topd, topi,
                        vis + mask.any(axis=1).astype(jnp.int32), st)

            init = (jnp.int32(0), topd_g, topi_g,
                    jnp.ones((Qg,), jnp.int32),   # the seed chunk counts
                    jnp.zeros(4, jnp.int32))
            _, topd_g, topi_g, vis, stw = jax.lax.while_loop(cond, body, init)
            return topd_g, topi_g, vis, stw

        if G == 1:
            topd, topi, vis, stw = walk(qs, order, lbi_s, lbk_s, topd, topi)
            return topd, topi, vis, st + stw
        # cluster: group queries by estimated work at the seed cutoff
        est = (lbi_all < topd[:, k - 1][:, None]).sum(axis=1)
        perm = jnp.argsort(est)
        inv = jnp.argsort(perm)
        Qg = Q // G
        parts = []
        for g in range(G):
            rows = perm[g * Qg:(g + 1) * Qg]
            parts.append(walk(qs[rows], order[rows], lbi_s[rows],
                              lbk_s[rows], topd[rows], topi[rows]))
        topd = jnp.concatenate([p[0] for p in parts])[inv]
        topi = jnp.concatenate([p[1] for p in parts])[inv]
        vis = jnp.concatenate([p[2] for p in parts])[inv]
        stw = sum(p[3] for p in parts)
        return topd, topi, vis, st + stw

    topd, topi, vis, st = jax.vmap(per_shard)(dev.db, dev.alive, dev.ids)
    topd, topi, vis, st = _mask_dead_shards(dev.shard_health,
                                            topd, topi, vis, st)
    S = topd.shape[0]
    alld = jnp.moveaxis(topd, 0, 1).reshape(Q, S * k)
    alli = jnp.moveaxis(topi, 0, 1).reshape(Q, S * k)
    d2m, idm = _dedup_topk(alld, alli, k)
    return jnp.sqrt(d2m), idm, vis.sum(axis=0), st.sum(axis=0), None


def _finalize_exact(index: DumpyIndex, qs: np.ndarray, ids_dev: np.ndarray,
                    k: int, metric: Metric = ED
                    ) -> tuple[np.ndarray, np.ndarray]:
    """k-sized host re-rank for bitwise parity with ``search.exact_search``:
    recompute candidate distances with the host math (direct-difference ED,
    or the float64 ``dtw_np`` DP the host heap compares) and sort by (d, id)
    — exactly the host heap's order.  Device invalid slots (``id -1``) stay
    padded as ``-1 / inf``; an empty collection returns all-padding for any
    metric."""
    Q, kk = ids_dev.shape
    if index.db.shape[0] == 0:                              # empty collection
        return (np.full((Q, k), -1, np.int64),
                np.full((Q, k), np.inf, np.float32))
    cand = index.db[np.maximum(ids_dev, 0)]                 # [Q, kk, n]
    if metric.is_dtw:
        # f64 vectorized DP, bitwise the scalar dtw_np per lane: heap order
        d = dtw_np_batch(qs, cand, metric.band)
        d = np.where(ids_dev < 0, np.inf, d)
    else:
        diff = cand - qs[:, None, :]
        d = np.sqrt((diff * diff).sum(axis=-1)).astype(np.float32)
        d = np.where(ids_dev < 0, np.inf, d)
    out_ids = np.full((Q, k), -1, np.int64)
    out_d = np.full((Q, k), np.inf, np.float32)
    for qi in range(Q):
        perm = np.lexsort((ids_dev[qi], d[qi]))[:k]
        perm = perm[np.isfinite(d[qi][perm])]
        out_ids[qi, :len(perm)] = ids_dev[qi][perm]
        out_d[qi, :len(perm)] = d[qi][perm]
    return out_ids, out_d


def _mesh_shards(mesh) -> int:
    s = 1
    for ax in ("pod", "data"):
        if mesh is not None and ax in mesh.axis_names:
            s *= mesh.shape[ax]
    return s


def exact_search_device_batch(index: DumpyIndex, qs: np.ndarray, k: int,
                              chunk: int = 2048, mesh=None,
                              dev: DeviceIndex | None = None,
                              metric: str | Metric = "ed",
                              band: int | None = None,
                              order: str | None = None,
                              return_stats: bool = False,
                              shard_health=None):
    """Batched exact kNN: ``qs [Q, n]`` → ``(ids [Q, k], d [Q, k],
    spans_visited [Q])``.  Results match ``search.exact_search`` at the same
    ``metric``/``band`` per query (fuzzy duplicates deduplicated on device,
    tombstones skipped, ``k > n_alive`` truncates); short results pad with
    ``id -1 / d inf``.

    With ``mesh`` (or a pre-sharded ``dev``), the span loop runs shard-local
    over the data axis and the per-shard top-k merges through an all-gather —
    bitwise-identical to the single-device result.  ``metric="dtw"`` shares
    the same (ED-width) device layout — spans are sub-blocked in-program to
    bound the DP frontier — and runs the LB_Keogh → LB_Improved → band-DP
    cascade under the candidate ordering ``order`` (defaults to the
    metric's, see ``core.metric.ORDERS``).  ``return_stats=True`` appends a
    per-stage cascade-counter dict (:data:`STAT_KEYS` + ``dp_survivors``)
    to the return tuple.

    ``shard_health`` (a length-``n_shards`` bool sequence, or a ``dev``
    whose ``shard_health`` is set) enables *degraded mode*: dead shards are
    masked out of the merge, results equal a healthy search restricted to
    the surviving shards' series, and the return tuple gains a trailing
    ``coverage`` float — the fraction of live series still reachable
    (docs/robustness.md).

    Each call is a ``dumpy.exact.call`` span (attributes ``call``, ``Q``,
    ``k``, ``chunk``, and ``scan``: ``"kernel"`` where the span-scan
    kernel walks the spans, ``"loop"`` where an XLA loop does) over
    ``dumpy.exact.prep``, ``.launch``, ``.wait`` and ``.finalize``, and
    records the span walk's :data:`WORK_KEYS` counters; the DTW lane
    program has none (docs/observability.md)."""
    Q = np.shape(qs)[0] if np.ndim(qs) == 2 else 1
    met = resolve(metric, index.n, band, order)
    scan = "kernel" if ops.span_scan_kernel(index.n) and not met.is_dtw \
        else "loop"
    with obs.span("dumpy.exact.call", call=next(_exact_calls), Q=Q, k=k,
                  chunk=chunk if dev is None else dev.chunk, scan=scan):
        with obs.span("dumpy.exact.prep"):
            qs = _validate_queries(qs, index.n)
            if dev is None:
                dev = index.device_index(chunk=chunk,
                                         n_shards=_mesh_shards(mesh),
                                         mesh=mesh)
            want_cov = shard_health is not None or \
                dev.shard_health is not None
            if shard_health is not None:
                dev = dev.with_shard_health(shard_health)
            sax = index.params.sax
            qs_dev = jnp.asarray(qs)
            prep, _ = _prep_batch(met, qs_dev, sax.w, sax.b)
        # +8 slack: the loop ranks by f32 device math (the MXU |q|²+|x|²-2qx
        # form for ED, the f32 band DP for DTW) whose rounding can swap
        # near-ties across the k boundary; the host re-rank then picks the
        # true top-k from the widened set
        kk = _result_margin(dev, k) + 8
        knn = _exact_knn_lane_sharded if (
            met.is_dtw and met.order != "shared") else _exact_knn_sharded

        def _launch():
            failpoint("search.shard_merge")
            return knn(dev, prep, qs_dev, k=kk, metric=met)

        with obs.span("dumpy.exact.launch"):
            _, ids, visited, st, work = with_retries(
                _launch, site="search.shard_merge")
        with obs.span("dumpy.exact.wait"):      # the one host fetch
            ids, visited, work, st = jax.device_get(
                (ids, visited, work, st if return_stats else None))
        if work is not None:
            for name, v in zip(WORK_KEYS, work):
                obs.count(name, v)
        with obs.span("dumpy.exact.finalize"):
            ids_out, d_out = _finalize_exact(index, qs, ids, k, met)
        out = [ids_out, d_out, visited]
        if want_cov:
            out.append(shard_coverage(index, dev))
        if return_stats:
            stats = dict(zip(STAT_KEYS, (int(v) for v in st)))
            stats["dp_survivors"] = int(st[0] - st[1] - st[2] - st[3])
            out.append(stats)
        return tuple(out)


def exact_search_device(index: DumpyIndex, q: np.ndarray, k: int,
                        chunk: int = 2048, metric: str | Metric = "ed",
                        band: int | None = None
                        ) -> tuple[np.ndarray, np.ndarray, int]:
    """Single-query exact kNN: a batch of one through the shared device
    path.  Returns (original ids, distances, spans visited)."""
    ids, d, visited = exact_search_device_batch(index, q.reshape(1, -1), k,
                                                chunk=chunk, metric=metric,
                                                band=band)
    valid = ids[0] >= 0
    return ids[0][valid], d[0][valid], int(visited[0])


# ---------------------------------------------------------------------------
# batched approximate search (vectorized root→leaf descent)
# ---------------------------------------------------------------------------

def _route_edges(sax_q: jax.Array, cur: jax.Array, node_csl: jax.Array,
                 node_shift: jax.Array, node_lam: jax.Array,
                 edge_parent: jax.Array, edge_sid: jax.Array,
                 edge_lb: jax.Array) -> jax.Array:
    """One routing step for a query batch sitting at internal nodes ``cur``:
    recompute each query's sid from the node's chosen segments (promoteiSAX
    bit extraction), match it against the node's edge span, and fall back to
    the min-LB child for empty regions — bit-for-bit the host descent
    including argmin tie-breaking.  Returns the taken edge index per query."""
    w = sax_q.shape[1]
    lam_max = node_csl.shape[1]
    pos = jnp.arange(lam_max)
    curc = jnp.clip(cur, 0, node_csl.shape[0] - 1)
    csl = node_csl[curc]                        # [Q, lam_max]
    shift = node_shift[curc]
    lam = node_lam[curc]
    segs = jnp.clip(csl, 0, w - 1)
    bits = (jnp.take_along_axis(sax_q, segs, axis=1) >> shift) & 1
    weights = jnp.where(
        pos[None, :] < lam[:, None],
        1 << jnp.maximum(lam[:, None] - 1 - pos[None, :], 0), 0)
    sid = (bits * weights).sum(axis=1)          # [Q]
    eligible = edge_parent[None, :] == curc[:, None]              # [Q, E]
    hit = eligible & (edge_sid[None, :] == sid[:, None])
    any_hit = hit.any(axis=1)
    hit_idx = jnp.argmax(hit, axis=1)
    fb_idx = jnp.argmin(jnp.where(eligible, edge_lb, jnp.inf), axis=1)
    return jnp.where(any_hit, hit_idx, fb_idx)


@functools.partial(jax.jit, static_argnames=("depth",))
def _descend_device(sax_q: jax.Array, node_csl: jax.Array,
                    node_shift: jax.Array, node_lam: jax.Array,
                    edge_parent: jax.Array, edge_sid: jax.Array,
                    edge_leaf: jax.Array, edge_child: jax.Array,
                    edge_lb: jax.Array, *, depth: int) -> jax.Array:
    """Lockstep root→leaf routing of a query batch over the flat tables —
    the host ``search.route_to_leaf`` vectorized (one step per tree level)."""
    Q = sax_q.shape[0]

    def step(_, carry):
        cur, leaf = carry                       # [Q]; leaf stays -1 en route
        active = leaf < 0
        e = _route_edges(sax_q, cur, node_csl, node_shift, node_lam,
                         edge_parent, edge_sid, edge_lb)
        nxt_leaf = edge_leaf[e]
        nxt_cur = edge_child[e]
        leaf = jnp.where(active, nxt_leaf, leaf)
        cur = jnp.where(active & (nxt_leaf < 0), nxt_cur, cur)
        return cur, leaf

    cur = jnp.zeros(Q, jnp.int32)
    leaf = jnp.full(Q, -1, jnp.int32)
    _, leaf = jax.lax.fori_loop(0, depth, step, (cur, leaf))
    return leaf


@functools.partial(jax.jit, static_argnames=("k", "kk", "nbr", "metric"))
def _leaf_topk_device(dev: DeviceIndex, qs: jax.Array, prep: tuple,
                      lbq: jax.Array, routed: jax.Array, *, k: int, kk: int,
                      nbr: int, metric: Metric = ED
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Scan the routed leaf (plus the ``nbr-1`` next-best leaves by the
    metric's leaf bound) of every query over the flattened ``[S·Tp, n]``
    shard layout and return the deduped top-k: ``(ids [Q,k], d2 [Q,k],
    leaves [Q,nbr])``.  Invalid slots come back as ``id -1 / d2 inf``.

    Leaves are scanned one rank at a time with a fused running top-k merge,
    so the peak temporary is ``[Q, lmax, n]`` — a monolithic
    ``[Q, nbr, lmax, n]`` gather would be hundreds of MB per decode step at
    serving defaults.  The running k-th best feeds the DTW cutoff, so later
    ranks prune against what earlier ranks already found."""
    Q = qs.shape[0]
    lmax = dev.lmax
    db_flat = dev.db.reshape(-1, dev.n)
    ids_flat = dev.ids.reshape(-1)
    alive_flat = dev.alive.reshape(-1)
    if dev.shard_health is not None:
        # degraded mode on the flattened view: rows of dead shards read as
        # tombstoned, so their candidates never enter a merge
        hm = jnp.asarray(dev.shard_health, bool)
        alive_flat = alive_flat & jnp.repeat(hm, dev.shard_rows)
    T = db_flat.shape[0]
    # routed leaf first (forced via -inf), then globally next-best leaves
    scores = lbq.at[jnp.arange(Q), routed].set(-jnp.inf)
    _, leaves = jax.lax.top_k(-scores, nbr)                  # [Q, nbr]

    def body(j, carry):
        topd, topi = carry
        starts = dev.leaf_start[leaves[:, j]]                # [Q] flattened
        sizes = dev.leaf_size[leaves[:, j]]
        rows = starts[:, None] + jnp.arange(lmax)[None, :]
        rows_c = jnp.clip(rows, 0, T - 1)                    # [Q, lmax]
        cand = db_flat[rows_c]                               # [Q, lmax, n]
        valid = (jnp.arange(lmax)[None, :] < sizes[:, None]) \
            & alive_flat[rows_c]
        d2 = _dist2_gather(metric, qs, prep, cand, valid, topd[:, kk - 1])
        idt = jnp.where(jnp.isinf(d2), -1, ids_flat[rows_c])
        return ops.topk_merge(topd, topi, d2, idt)

    init = (jnp.full((Q, kk), jnp.inf, jnp.float32),
            jnp.full((Q, kk), -1, jnp.int32))
    topd, topi = jax.lax.fori_loop(0, nbr, body, init)
    d2f, idf = _dedup_topk(topd, topi, k)                    # segment-min dedup
    return idf, d2f, leaves


@functools.partial(jax.jit, static_argnames=("k", "kk", "nbr", "metric"))
def _approx_knn_device(dev: DeviceIndex, prep: tuple, sax_q: jax.Array,
                       qs: jax.Array, *, k: int, kk: int, nbr: int,
                       metric: Metric = ED
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The whole approximate path as one device program (descent + leaf
    scan): the jit entry point the compile-contract audit registers
    (``repro.analysis.registry``).  Returns ``(ids [Q,k], d2 [Q,k],
    leaves [Q,nbr])``; a degenerate tree (the root is the only leaf) routes
    every query to leaf 0, exactly as the host path."""
    lbq = _interval_lb(dev, prep[0], prep[1], dev.leaf_lo_g, dev.leaf_hi_g)
    if dev.node_lam.shape[0] == 0:   # degenerate tree: the root is the only leaf
        routed = jnp.zeros(qs.shape[0], jnp.int32)
    else:
        edge_lb = _interval_lb(dev, prep[0], prep[1], dev.rt_lo, dev.rt_hi)
        routed = _descend_device(
            sax_q, dev.node_csl, dev.node_shift, dev.node_lam,
            dev.rt_parent, dev.rt_sid, dev.rt_leaf, dev.rt_child,
            edge_lb, depth=dev.depth)
    return _leaf_topk_device(dev, qs, prep, lbq, routed, k=k, kk=kk,
                             nbr=nbr, metric=metric)


def approximate_search_device_batch(index: DumpyIndex, qs: np.ndarray, k: int,
                                    nbr: int = 1,
                                    dev: DeviceIndex | None = None,
                                    metric: str | Metric = "ed",
                                    band: int | None = None
                                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batched approximate kNN (paper §5.5 descent, vectorized over queries).

    ``nbr=1`` visits exactly the leaf the host ``approximate_search`` picks
    at the same metric (leaf-selection parity is tested).  ``nbr>1`` widens
    to the next-best leaves by the metric's leaf bound — the serving recall
    knob; unlike host ``extended_search`` the extras are chosen globally,
    not within the target subtree.  Returns ``(ids [Q, k'], d [Q, k'],
    leaves [Q, nbr])`` with ``k' = min(k, nbr·max_leaf_size)``; empty slots
    are ``id -1 / d inf``.  Fuzzy replicas sharing a leaf are deduped in
    the device merge — the whole path stays on device."""
    qs = _validate_queries(qs, index.n)
    met = resolve(metric, qs.shape[1], band)
    if dev is None:
        dev = index.device_index()
    sax_p = index.params.sax
    qs_dev = jnp.asarray(qs)
    prep, sax_q = _prep_batch(met, qs_dev, sax_p.w, sax_p.b)

    nbr = min(nbr, dev.n_leaves)
    # fuzzy replicas can share a leaf (sibling packing merges them), so merge
    # with the duplicate margin and segment-min-dedup on device
    kk = min(_result_margin(dev, k), nbr * dev.lmax)
    k_out = min(k, nbr * dev.lmax)
    ids, d2, leaves = _approx_knn_device(dev, prep, sax_q, qs_dev,
                                         k=k_out, kk=kk, nbr=nbr, metric=met)
    return (np.asarray(ids).astype(np.int64), np.sqrt(np.asarray(d2)),
            np.asarray(leaves))


# ---------------------------------------------------------------------------
# batched extended search — Algorithm 4 (sibling subtrees, LB-ordered)
# ---------------------------------------------------------------------------

def _descend_subtree(dev: DeviceIndex, sax_q: jax.Array, edge_lb: jax.Array,
                     *, nbr: int) -> tuple[jax.Array, jax.Array]:
    """Root→subtree descent of a query batch: follow sids (min-LB fallback on
    empty regions) while the child subtree still holds more than ``nbr``
    leaves.  Returns ``(parent node id [Q], stop edge index [Q])`` — the stop
    edge's target is the host descent's stop node, its parent the node whose
    children form the sibling set."""
    Q = sax_q.shape[0]

    def step(_, carry):
        cur, pm, se, done = carry
        e = _route_edges(sax_q, cur, dev.node_csl, dev.node_shift,
                         dev.node_lam, dev.rt_parent, dev.rt_sid, edge_lb)
        stop = (~done) & ((dev.rt_leaf[e] >= 0) | (dev.rt_nl[e] <= nbr))
        curc = jnp.clip(cur, 0, dev.node_csl.shape[0] - 1)
        pm = jnp.where(stop, curc, pm)
        se = jnp.where(stop, e, se)
        done = done | stop
        cur = jnp.where(done, cur, dev.rt_child[e])
        return cur, pm, se, done

    init = (jnp.zeros(Q, jnp.int32), jnp.zeros(Q, jnp.int32),
            jnp.zeros(Q, jnp.int32), jnp.zeros(Q, bool))
    _, pm, se, _ = jax.lax.fori_loop(0, dev.depth, step, init)
    return pm, se


def _sibling_schedule(dev: DeviceIndex, prep: tuple, lbq: jax.Array,
                      pm: jax.Array, se: jax.Array, *, nbr: int,
                      span_cap: int) -> jax.Array:
    """Per-query leaf visit schedule ``[Q, nbr]`` over the stop subtree.

    Mirrors the host order exactly: the target subtree (the stop edge's
    span) ranks first, the remaining siblings of the parent group by
    (interval MINDIST, span begin), and leaves inside every subtree by
    (leaf LB, leaf id); the overall schedule is the ``nbr`` smallest
    (sibling rank, leaf LB, leaf id) keys, which equals the host's
    budget-truncated walk because sibling spans partition the parent span.

    The sort runs over a per-query window of ``span_cap`` leaf ids starting
    at the stop subtree's span begin — subtree spans are contiguous, and
    ``span_cap`` (``FlatRouting.stop_span_cap``) bounds every reachable
    parent span, so the window always covers the schedulable leaves without
    lexsorting all ``L`` leaves per query (ROADMAP: schedule width)."""
    Q, L = lbq.shape
    gmax = dev.gmax
    seg_lo, seg_hi = prep[0], prep[1]
    i32max = jnp.iinfo(jnp.int32).max
    tb = dev.rt_begin[se]                                     # [Q]
    goff = dev.grp_off[pm]
    gcnt = dev.grp_off[pm + 1] - goff
    gi = goff[:, None] + jnp.arange(gmax, dtype=jnp.int32)[None, :]
    gi = jnp.clip(gi, 0, dev.grp_begin.shape[0] - 1)          # [Q, gmax]
    valid = jnp.arange(gmax)[None, :] < gcnt[:, None]
    m_begin = jnp.where(valid, dev.grp_begin[gi], i32max)
    # member interval MINDIST (squared — order-equal to the host sqrt form)
    below = jnp.maximum(dev.grp_lo[gi] - seg_hi[:, None, :], 0.0)
    above = jnp.maximum(seg_lo[:, None, :] - dev.grp_hi[gi], 0.0)
    d = jnp.maximum(below, above)
    sib_lb = (dev.n / dev.w) * (d * d).sum(-1)                # [Q, gmax]
    sib_lb = jnp.where(valid, sib_lb, jnp.inf)
    sib_lb = jnp.where(m_begin == tb[:, None], -jnp.inf, sib_lb)
    # member visit rank: (LB, span begin), target forced first by the -inf
    perm = jnp.lexsort((m_begin, sib_lb), axis=-1)
    rank = jnp.argsort(perm, axis=-1).astype(jnp.int32)       # inverse perm
    SW = min(max(int(span_cap), 1), L)
    if SW >= L:
        # cap covers every leaf (a stop parent near the root): the window
        # gathers buy nothing — rank all leaves directly as before
        leaf_ids = jnp.arange(L, dtype=jnp.int32)
        sidx = jax.vmap(lambda mb: jnp.searchsorted(
            mb, leaf_ids, side="right"))(m_begin) - 1
        sidx = jnp.clip(sidx, 0, gmax - 1)
        leaf_rank = jnp.take_along_axis(rank, sidx, axis=1)   # [Q, L]
        under = (leaf_ids[None, :] >= dev.node_begin[pm][:, None]) & \
                (leaf_ids[None, :] < dev.node_end[pm][:, None])
        leaf_rank = jnp.where(under, leaf_rank, gmax + 1)
        order = jnp.lexsort((lbq, leaf_rank), axis=-1)        # stable → id
        return order[:, :nbr].astype(jnp.int32)
    # per-query window of candidate leaves: the parent span is contiguous
    # and at most span_cap wide, so [begin, begin + span_cap) covers it
    win = dev.node_begin[pm][:, None] \
        + jnp.arange(SW, dtype=jnp.int32)[None, :]            # [Q, SW]
    winc = jnp.clip(win, 0, L - 1)
    lbw = jnp.take_along_axis(lbq, winc, axis=1)
    # owning member of every window leaf: spans are begin-sorted and
    # partition the parent span, so one searchsorted per query resolves it
    sidx = jax.vmap(
        lambda mb, wi: jnp.searchsorted(mb, wi, side="right"))(m_begin,
                                                               winc) - 1
    sidx = jnp.clip(sidx, 0, gmax - 1)
    leaf_rank = jnp.take_along_axis(rank, sidx, axis=1)       # [Q, SW]
    under = win < dev.node_end[pm][:, None]   # win >= begin by construction
    leaf_rank = jnp.where(under, leaf_rank, gmax + 1)
    order = jnp.lexsort((lbw, leaf_rank), axis=-1)            # stable → id
    sel = order[:, :nbr]
    return jnp.take_along_axis(winc, sel, axis=1).astype(jnp.int32)


def _scan_leaf_schedule(dev: DeviceIndex, qs: jax.Array, prep: tuple,
                        leaves: jax.Array, *, k: int, metric: Metric = ED
                        ) -> tuple[jax.Array, jax.Array]:
    """Visit the per-query leaf schedule shard-locally and merge.

    Each shard owns the contiguous leaf range ``leaf_bounds[s:s+2]`` of the
    leaf-aligned layout; it scans only the scheduled leaves inside that range
    (the rest mask to ``+inf``), producing a local ``[Q, k]`` top-k.  The
    ``[S, Q, k]`` locals then merge exactly like the exact path: transpose/
    reshape (the all-gather under a ``data`` sharding) + segment-min dedup +
    top-k — so results are bitwise invariant to the shard count.  Candidate
    distances go through :func:`_dist2_gather`, so DTW candidates prune by
    LB_Keogh against the shard-local running k-th best."""
    Q, nbr = leaves.shape
    lmax, n, L = dev.lmax, dev.n, dev.n_leaves
    S, Tp = dev.n_shards, dev.shard_rows
    row0 = jnp.asarray([s * Tp for s in range(S)], jnp.int32)
    lcut = jnp.asarray(dev.leaf_bounds, jnp.int32)

    def per_shard(db_s, alive_s, ids_s, r0, a, z):
        def body(j, carry):
            topd, topi = carry
            lf = leaves[:, j]                                 # [Q]
            mine = (lf >= a) & (lf < z)
            lfc = jnp.clip(lf, 0, L - 1)
            starts = dev.leaf_start[lfc] - r0                 # shard-local
            sizes = jnp.where(mine, dev.leaf_size[lfc], 0)
            rows = starts[:, None] + jnp.arange(lmax)[None, :]
            rows_c = jnp.clip(rows, 0, Tp - 1)                # [Q, lmax]
            cand = db_s[rows_c]                               # [Q, lmax, n]
            val = (jnp.arange(lmax)[None, :] < sizes[:, None]) \
                & alive_s[rows_c]
            d2 = _dist2_gather(metric, qs, prep, cand, val, topd[:, k - 1])
            idt = jnp.where(jnp.isinf(d2), -1, ids_s[rows_c])
            return ops.topk_merge(topd, topi, d2, idt)

        init = (jnp.full((Q, k), jnp.inf, jnp.float32),
                jnp.full((Q, k), -1, jnp.int32))
        return jax.lax.fori_loop(0, nbr, body, init)

    topd, topi = jax.vmap(per_shard)(dev.db, dev.alive, dev.ids,
                                     row0, lcut[:-1], lcut[1:])
    topd, topi, _, _ = _mask_dead_shards(dev.shard_health, topd, topi)
    alld = jnp.moveaxis(topd, 0, 1).reshape(Q, S * k)
    alli = jnp.moveaxis(topi, 0, 1).reshape(Q, S * k)
    return _dedup_topk(alld, alli, k)


@functools.partial(jax.jit,
                   static_argnames=("k", "nbr", "subtree", "metric",
                                    "span_cap"))
def _extended_knn_sharded(dev: DeviceIndex, prep: tuple,
                          sax_q: jax.Array, qs: jax.Array, *, k: int,
                          nbr: int, subtree: bool, metric: Metric = ED,
                          span_cap: int = 0
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched Alg. 4 as one XLA program: descent → sibling schedule →
    shard-local scan → all-gather dedup merge.  With ``subtree=False`` (the
    whole tree fits the ``nbr`` budget, or the root is the only leaf) the
    schedule is simply every leaf by (LB, leaf id) — the host's
    ``parent is None`` branch.  All bounds are the metric's interval
    MINDIST; ``span_cap`` bounds the per-query schedule sort width."""
    lbq = _interval_lb(dev, prep[0], prep[1], dev.leaf_lo_g, dev.leaf_hi_g)
    if subtree:
        edge_lb = _interval_lb(dev, prep[0], prep[1], dev.rt_lo, dev.rt_hi)
        pm, se = _descend_subtree(dev, sax_q, edge_lb, nbr=nbr)
        leaves = _sibling_schedule(dev, prep, lbq, pm, se, nbr=nbr,
                                   span_cap=span_cap or dev.n_leaves)
    else:
        order = jnp.argsort(lbq, axis=-1)                     # stable → id
        leaves = order[:, :nbr].astype(jnp.int32)
    d2, ids = _scan_leaf_schedule(dev, qs, prep, leaves, k=k, metric=metric)
    return d2, ids, leaves


def extended_search_device_batch(index: DumpyIndex, qs: np.ndarray, k: int,
                                 nbr: int = 1, chunk: int = 2048, mesh=None,
                                 dev: DeviceIndex | None = None,
                                 rerank: bool = True,
                                 metric: str | Metric = "ed",
                                 band: int | None = None,
                                 shard_health=None):
    """Batched extended approximate kNN (paper Alg. 4, vectorized over
    queries): ``qs [Q, n]`` → ``(ids [Q, k], d [Q, k], leaves [Q, nbr'])``
    with ``nbr' = min(nbr, n_leaves)``; short results pad ``id -1 / d inf``.

    The visit set per query is exactly the host ``extended_search`` schedule
    at the same metric (target subtree first, then LB-ordered siblings,
    LB-ordered leaves within), so ``nbr=1`` degenerates to the approximate
    answer and the k-th distance is monotone in ``nbr``.  With ``mesh`` (or
    a pre-sharded ``dev``) the leaf scan runs shard-local and merges through
    the same all-gather + segment-min dedup as the exact path — bitwise
    invariant to the shard count.  The per-query schedule sorts only the
    stop subtree's contiguous span (``FlatRouting.stop_span_cap``), not all
    ``L`` leaves.

    ``rerank=True`` (default) finishes with the k-sized host re-rank for
    bitwise (ids, dists) parity with ``extended_search``; serving passes
    ``rerank=False`` to keep the whole path on device (ids ordered by the
    device d², distances returned as ``sqrt`` of the device form).

    ``shard_health`` enables degraded mode exactly as in
    :func:`exact_search_device_batch` (dead shards masked from the scan and
    merge; a trailing ``coverage`` float joins the return tuple)."""
    qs = _validate_queries(qs, index.n)
    met = resolve(metric, qs.shape[1], band)
    if dev is None:
        dev = index.device_index(chunk=chunk, n_shards=_mesh_shards(mesh),
                                 mesh=mesh)
    want_cov = shard_health is not None or dev.shard_health is not None
    if shard_health is not None:
        dev = dev.with_shard_health(shard_health)
    sax_p = index.params.sax
    qs_dev = jnp.asarray(qs)
    prep, sax_q = _prep_batch(met, qs_dev, sax_p.w, sax_p.b)
    L = dev.n_leaves
    nbr_eff = max(min(int(nbr), L), 1)
    subtree = dev.node_lam.shape[0] > 0 and L > nbr_eff
    span_cap = index.routing_flat.stop_span_cap(nbr_eff) if subtree else 0
    kk = _result_margin(dev, k) + (8 if rerank else 0)
    d2, ids, leaves = _extended_knn_sharded(dev, prep, sax_q, qs_dev,
                                            k=kk, nbr=nbr_eff,
                                            subtree=subtree, metric=met,
                                            span_cap=span_cap)
    if rerank:
        ids_out, d_out = _finalize_exact(index, qs, np.asarray(ids), k, met)
        out = [ids_out, d_out, np.asarray(leaves)]
    else:
        out = [np.asarray(ids)[:, :k].astype(np.int64),
               np.sqrt(np.asarray(d2))[:, :k], np.asarray(leaves)]
    if want_cov:
        out.append(shard_coverage(index, dev))
    return tuple(out)


# ---------------------------------------------------------------------------
# bucketed serving search — one compiled program per bucket shape, every
# per-request knob (k / nbr / metric / liveness) a *traced* lane array, so a
# coalescing front-end never recompiles across mixed workloads
# (docs/serving.md: the masking contract)
# ---------------------------------------------------------------------------

def _dist2_gather_mixed(qs: jax.Array, prep: tuple, cand: jax.Array,
                        valid: jax.Array, cutoff2: jax.Array,
                        lane_dtw: jax.Array, band: int, has_dtw: bool
                        ) -> jax.Array:
    """Per-lane metric blend of :func:`_dist2_gather`: ED lanes pay the
    plain squared-distance form, DTW lanes the LB_Keogh → LB_Improved →
    masked band DP cascade.

    ``has_dtw`` is the *host-level* ``lane_dtw.any()``, threaded as a
    static: an all-ED bucket compiles a pure-ED scan body with no DTW code
    at all.  An in-program ``lax.cond`` was measured ~30% slower even
    untaken — the cond in the inner scan loop blocks XLA from fusing the
    gather→distance→merge pipeline — so the metric *presence* specializes
    the program (exactly two variants per bucket shape, both warmed by the
    front-end) while the per-lane metric *assignment* stays traced.

    Bitwise per lane: with ``lane_dtw[q]`` fixed, lane q's expression is
    exactly the :func:`_dist2_gather` of that metric — the blend only
    selects between the two results, never mixes them."""
    d2_ed = jnp.where(valid & ~lane_dtw[:, None],
                      ((cand - qs[:, None, :]) ** 2).sum(-1), jnp.inf)
    if not has_dtw:
        return d2_ed
    _, _, env_lo, env_hi = prep
    lbk2 = lb_keogh2_batch_jnp(cand, env_hi, env_lo)
    lbi2 = lb_improved2_batch_jnp(cand, qs, env_hi, env_lo, band)
    mask = valid & lane_dtw[:, None] \
        & (lbk2 < cutoff2[:, None]) & (lbi2 < cutoff2[:, None])
    d2_dtw = dtw2_masked_gather_jnp(qs, cand, band, mask, cutoff2)
    return jnp.where(lane_dtw[:, None], d2_dtw, d2_ed)


def _scan_bucket_schedule(dev: DeviceIndex, qs: jax.Array, prep: tuple,
                          leaves: jax.Array, lane_nbr: jax.Array,
                          lane_dtw: jax.Array, *, k: int, band: int,
                          has_dtw: bool) -> tuple[jax.Array, jax.Array]:
    """:func:`_scan_leaf_schedule` with per-lane masking: schedule rank ``j``
    is scanned for lane q only while ``j < lane_nbr[q]`` (a dead/padded lane
    has ``lane_nbr == 0`` and scans nothing — its gathers still execute but
    every candidate masks to ``+inf``), and the candidate distance blends
    ED and the DTW cascade per lane (:func:`_dist2_gather_mixed`)."""
    Q, nbr = leaves.shape
    lmax, L = dev.lmax, dev.n_leaves
    S, Tp = dev.n_shards, dev.shard_rows
    row0 = jnp.asarray([s * Tp for s in range(S)], jnp.int32)
    lcut = jnp.asarray(dev.leaf_bounds, jnp.int32)

    def per_shard(db_s, alive_s, ids_s, r0, a, z):
        def body(j, carry):
            topd, topi = carry
            lf = leaves[:, j]                                 # [Q]
            mine = (lf >= a) & (lf < z) & (j < lane_nbr)
            lfc = jnp.clip(lf, 0, L - 1)
            starts = dev.leaf_start[lfc] - r0                 # shard-local
            sizes = jnp.where(mine, dev.leaf_size[lfc], 0)
            rows = starts[:, None] + jnp.arange(lmax)[None, :]
            rows_c = jnp.clip(rows, 0, Tp - 1)                # [Q, lmax]
            cand = db_s[rows_c]                               # [Q, lmax, n]
            val = (jnp.arange(lmax)[None, :] < sizes[:, None]) \
                & alive_s[rows_c]
            d2 = _dist2_gather_mixed(qs, prep, cand, val, topd[:, k - 1],
                                     lane_dtw, band, has_dtw)
            idt = jnp.where(jnp.isinf(d2), -1, ids_s[rows_c])
            return ops.topk_merge(topd, topi, d2, idt)

        init = (jnp.full((Q, k), jnp.inf, jnp.float32),
                jnp.full((Q, k), -1, jnp.int32))
        return jax.lax.fori_loop(0, nbr, body, init)

    topd, topi = jax.vmap(per_shard)(dev.db, dev.alive, dev.ids,
                                     row0, lcut[:-1], lcut[1:])
    topd, topi, _, _ = _mask_dead_shards(dev.shard_health, topd, topi)
    alld = jnp.moveaxis(topd, 0, 1).reshape(Q, S * k)
    alli = jnp.moveaxis(topi, 0, 1).reshape(Q, S * k)
    return _dedup_topk(alld, alli, k)


@functools.partial(jax.jit,
                   static_argnames=("kk", "nbr_max", "subtree", "band",
                                    "span_cap", "has_dtw"))
def _bucket_knn_sharded(dev: DeviceIndex, prep_ed: tuple, prep_dtw: tuple,
                        sax_q: jax.Array, qs: jax.Array,
                        lane_nbr: jax.Array, lane_dtw: jax.Array, *,
                        kk: int, nbr_max: int, subtree: bool, band: int,
                        span_cap: int, has_dtw: bool
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The bucketed serving program: extended (Alg. 4) search where every
    per-request knob is a traced lane array, so one compiled program per
    bucket shape serves any ``k``/``nbr``/``metric`` mix.

    - ``lane_nbr [Q] i32`` — per-lane leaf budget; 0 marks a dead (padding)
      lane.  Threads through the descent stop test elementwise, masks the
      schedule scan, and selects flat-vs-subtree per lane (``nbr >= L``
      lanes take the all-leaves flat order, the host path's
      ``subtree=False`` branch).
    - ``lane_dtw [Q] bool`` — per-lane metric.  The two metric preps are
      shape-identical tuples; blending rows with ``jnp.where`` makes every
      LB / descent / schedule expression per-lane-correct for free, and the
      candidate distance blends via :func:`_dist2_gather_mixed`.
    - per-lane ``k`` never reaches the device: the program runs at the full
      dedup margin ``kk`` and the host truncates each lane (the superset
      argument in docs/serving.md).

    Statics ``kk``/``nbr_max``/``subtree``/``band``/``span_cap`` are
    bucket-ladder constants; ``has_dtw`` (host-level ``lane_dtw.any()``)
    splits each bucket shape into a pure-ED and a mixed variant — both
    warmed up front, so the recompile gate still proves the warm cache key
    never depends on per-request knob *values*."""
    sel = lane_dtw[:, None]
    prep = tuple(jnp.where(sel, pd, pe)
                 for pe, pd in zip(prep_ed, prep_dtw))
    lbq = _interval_lb(dev, prep[0], prep[1], dev.leaf_lo_g, dev.leaf_hi_g)
    L = dev.n_leaves
    flat = jnp.argsort(lbq, axis=-1)[:, :nbr_max].astype(jnp.int32)
    if subtree:
        edge_lb = _interval_lb(dev, prep[0], prep[1], dev.rt_lo, dev.rt_hi)
        pm, se = _descend_subtree(dev, sax_q, edge_lb, nbr=lane_nbr)
        sub = _sibling_schedule(dev, prep, lbq, pm, se, nbr=nbr_max,
                                span_cap=span_cap)
        leaves = jnp.where((lane_nbr >= L)[:, None], flat, sub)
    else:
        leaves = flat
    d2, ids = _scan_bucket_schedule(dev, qs, prep, leaves, lane_nbr,
                                    lane_dtw, k=kk, band=band,
                                    has_dtw=has_dtw)
    return d2, ids, leaves


def bucket_search_launch(index: DumpyIndex, qs_dev: jax.Array,
                         lane_nbr, lane_dtw, *, k_max: int, nbr_max: int,
                         band: int | None = None,
                         dev: DeviceIndex | None = None
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Launch the bucketed program on an already-staged device query batch —
    the async half of :func:`bucket_search_device_batch`.  JAX async
    dispatch returns immediately, so a front-end stages bucket *i+1* while
    this bucket computes and only blocks in :func:`bucket_search_finish`.

    ``lane_nbr [Q]`` is the per-request leaf budget with 0 marking dead
    (padding) lanes; ``lane_dtw [Q] bool`` selects the metric per lane.
    Returns device arrays ``(d2 [Q, kk], ids [Q, kk], leaves [Q, nbr'])``
    at the full dedup margin ``kk = _result_margin(dev, k_max)``."""
    if dev is None:
        dev = index.device_index()
    sax_p = index.params.sax
    band_eff = max(int(band) if band is not None else default_band(dev.n), 1)
    paa_q, sax_q = _encode_batch(qs_dev, sax_p.w, sax_p.b)
    prep_ed = query_prep_jnp(ED, qs_dev, paa_q)
    lane_dtw = np.asarray(lane_dtw, bool)
    has_dtw = bool(lane_dtw.any())
    if has_dtw:
        prep_dtw = query_prep_jnp(Metric("dtw", band_eff), qs_dev, paa_q)
    else:
        prep_dtw = prep_ed      # no DTW lane: values unused, shapes identical
    L = dev.n_leaves
    nbr_eff = max(min(int(nbr_max), L), 1)
    subtree = dev.node_lam.shape[0] > 0 and L > 1
    # the cap is monotone in nbr and the schedule is cap-invariant, so the
    # lane maximum covers every lane's stop parent (docs/serving.md)
    span_cap = index.routing_flat.stop_span_cap(nbr_eff) if subtree else 0
    kk = _result_margin(dev, k_max)
    lane_nbr = np.clip(np.asarray(lane_nbr, np.int64), 0, nbr_eff)
    return _bucket_knn_sharded(
        dev, prep_ed, prep_dtw, sax_q.astype(jnp.int32), qs_dev,
        jnp.asarray(lane_nbr, jnp.int32), jnp.asarray(lane_dtw),
        kk=kk, nbr_max=nbr_eff, subtree=subtree, band=band_eff,
        span_cap=span_cap, has_dtw=has_dtw)


def bucket_search_finish(res, lane_k, lane_nbr, *, k_max: int
                         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Harvest a :func:`bucket_search_launch` result on host: block on the
    device arrays, truncate every lane to its own ``k`` (columns ≥ k pad
    ``-1 / inf``) and its schedule to its own ``nbr`` (pad ``-1``).  The
    first ``lane_k[q]`` columns are bitwise the ids/distances
    ``extended_search_device_batch(rerank=False)`` returns for that request
    issued alone (docs/serving.md: the masking contract)."""
    d2, ids, leaves = res
    ids = np.asarray(ids)[:, :k_max].astype(np.int64)
    d = np.sqrt(np.asarray(d2)[:, :k_max]).astype(np.float32)
    leaves = np.asarray(leaves)
    kcol = np.arange(k_max)[None, :] < np.asarray(lane_k, np.int64)[:, None]
    ids = np.where(kcol, ids, -1)
    d = np.where(kcol, d, np.inf).astype(np.float32)
    ncol = np.arange(leaves.shape[1])[None, :] \
        < np.asarray(lane_nbr, np.int64)[:, None]
    return ids, d, np.where(ncol, leaves, -1)


def bucket_search_device_batch(index: DumpyIndex, qs, ks, nbrs,
                               metrics=None, *, k_max: int | None = None,
                               nbr_max: int | None = None,
                               band: int | None = None, chunk: int = 2048,
                               mesh=None, dev: DeviceIndex | None = None,
                               shard_health=None):
    """Coalesced mixed-knob kNN: one device program per batch shape, every
    per-request knob a lane array — the blocking entry point behind the
    serving front-end (``repro.serving.batching``).

    ``ks``/``nbrs`` give each lane its own ``k`` and leaf budget; a lane
    with ``ks[q] == 0`` is a dead (padding) lane — its query must still be
    finite (pad with zeros) and its result is all ``-1 / inf``.  ``metrics``
    is a per-lane ``"ed"``/``"dtw"`` sequence (or a bool DTW mask; default
    all-ED); ``band`` is the shared DTW band (default ``0.1 n``, matching
    ``resolve``).  ``k_max``/``nbr_max`` pin the program's static widths so
    a front-end can hold them constant across calls (defaults: the lane
    maxima).

    Lane q's live columns are bitwise
    ``extended_search_device_batch(index, qs[q:q+1], ks[q], nbr=nbrs[q],
    metric=..., rerank=False)`` — masking, never recompilation, absorbs the
    knob mix (the parity tests in ``tests/test_serving_batching.py`` pin
    this, including degraded ``shard_health`` and fuzzy+tombstone layouts).
    Validation is one vectorized pass for the whole batch.

    ``shard_health`` enables degraded mode exactly as in
    :func:`exact_search_device_batch` (dead shards masked from scan and
    merge; a trailing ``coverage`` float joins the return tuple)."""
    qs = _validate_queries(qs, index.n)   # one vectorized check per batch
    Q = qs.shape[0]
    ks = np.asarray(ks, np.int64).reshape(-1)
    nbrs = np.asarray(nbrs, np.int64).reshape(-1)
    if ks.shape[0] != Q or nbrs.shape[0] != Q:
        raise ValueError(
            f"ks/nbrs need one entry per query lane: got {ks.shape[0]}/"
            f"{nbrs.shape[0]} for {Q} lanes")
    if (ks < 0).any() or (nbrs < 0).any():
        raise ValueError("per-lane k/nbr must be >= 0 (0 = dead lane)")
    if metrics is None:
        lane_dtw = np.zeros(Q, bool)
    else:
        ms = list(metrics)
        if len(ms) != Q:
            raise ValueError(
                f"metrics needs one entry per query lane: got {len(ms)} "
                f"for {Q} lanes")
        lane_dtw = np.empty(Q, bool)
        for i, m in enumerate(ms):
            if isinstance(m, (bool, np.bool_, int, np.integer)):
                lane_dtw[i] = bool(m)
            elif m in ("ed", "dtw"):
                lane_dtw[i] = m == "dtw"
            else:
                raise ValueError(f"lane {i}: unknown metric {m!r}")
    k_max = int(k_max) if k_max is not None else max(int(ks.max()), 1)
    nbr_max = int(nbr_max) if nbr_max is not None else max(int(nbrs.max()), 1)
    over = np.where(ks > k_max)[0]
    if over.size:
        raise ValueError(
            f"lanes {over[:8].tolist()} request k > k_max={k_max}")
    if dev is None:
        dev = index.device_index(chunk=chunk, n_shards=_mesh_shards(mesh),
                                 mesh=mesh)
    want_cov = shard_health is not None or dev.shard_health is not None
    if shard_health is not None:
        dev = dev.with_shard_health(shard_health)
    if index.db.shape[0] == 0:                              # empty collection
        out = [np.full((Q, k_max), -1, np.int64),
               np.full((Q, k_max), np.inf, np.float32),
               np.full((Q, max(nbr_max, 1)), -1, np.int32)]
        if want_cov:
            out.append(shard_coverage(index, dev))
        return tuple(out)
    alive = ks > 0
    nbr_eff = max(min(nbr_max, dev.n_leaves), 1)
    lane_nbr = np.where(alive, np.clip(nbrs, 1, nbr_eff), 0)
    lane_dtw = lane_dtw & alive        # dead lanes stay on the ED fast path
    qs_dev = jnp.asarray(qs)

    def _launch():
        failpoint("search.shard_merge")
        return bucket_search_launch(index, qs_dev, lane_nbr, lane_dtw,
                                    k_max=k_max, nbr_max=nbr_max,
                                    band=band, dev=dev)

    res = with_retries(_launch, site="search.shard_merge")
    ids, d, leaves = bucket_search_finish(
        res, np.where(alive, np.minimum(ks, k_max), 0), lane_nbr,
        k_max=k_max)
    out = [ids, d, leaves]
    if want_cov:
        out.append(shard_coverage(index, dev))
    return tuple(out)
