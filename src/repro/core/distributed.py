"""Distributed Dumpy: index building and search on the production mesh.

The paper's Algorithm 1 maps onto the mesh as follows (DESIGN.md §2):

* **Stage 1 (SAX table)** — the collection shards over the ``data`` axis;
  ``sax_encode`` (Pallas kernel) runs shard-local.  This is the pass whose
  disk I/O dominated the original; here it is one embarrassingly-parallel
  device program.
* **Root histogram** — next-bit codes → ``bincount(2^w)`` shard-local,
  summed by GSPMD's all-reduce (the histogram is 256 KB — the *only*
  cross-device traffic the global split decision needs; this is why split-
  from-global-statistics is cheap on a pod while iSAX2+'s split-on-overflow
  never sees global data).
* **Subtree builds** — after the root split, sid-partitioned subsets are
  independent; hosts build their partitions in parallel (single-controller
  here: host loop over partitions).
* **Search** — the ``DeviceIndex`` shards the ordered collection leaf-aligned
  over ``data`` (leaf/routing tables replicate; they are MBs).  Each device
  runs the windowed-pruning span loop on its shard and emits (kk ids, kk
  distances); an all-gather + fused top-k merge (with segment-min dedup over
  original ids) combines them on device — see ``core/search_device.py``.

``build_step`` / ``search_step`` and the ``lower_*`` helpers below lower
every program at fixed shapes on a given mesh; the compile-contract audit
(``repro.analysis.registry``) lowers the shipped programs through them.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import get_mesh
from .build import DumpyParams
from .device_index import data_axes, mesh_shards
from .index import DumpyIndex
from .sax import next_bit_codes_jnp, sax_encode_jnp


# ---------------------------------------------------------------------------
# device programs (jit-able; lowered by the ``lower_*`` helpers)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def build_step(db_shard: jax.Array, w: int, b: int
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stage 1 + root histogram for one (sharded) collection.

    Returns (paa, sax, hist).  Under a mesh with ``db`` batch-sharded, the
    bincount partials are combined by one all-reduce of 2^w ints.
    """
    paa, sax = sax_encode_jnp(db_shard, w, b)
    codes = next_bit_codes_jnp(sax, jnp.zeros((w,), jnp.int32), w, b)
    hist = jnp.bincount(codes, length=1 << w)
    return paa, sax, hist


@functools.partial(jax.jit, static_argnums=(4,))
def search_step(q: jax.Array, db_ordered: jax.Array, leaf_lo: jax.Array,
                leaf_hi: jax.Array, k: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-shot device kNN: LB-scan over the leaf table + exact distances.

    ``lower_search_oneshot`` lowers this with ``db_ordered`` sharded over
    ``data`` — GSPMD emits the cross-shard top-k combine.  The third output
    is the ``[Q]``-shaped per-query min squared lower bound over the leaf
    table (the pruning statistic; its sqrt lower-bounds each query's true
    nearest distance)."""
    from .lb import ed2_batch_jnp, mindist_jnp
    n = db_ordered.shape[1]
    paa_q = q.reshape(q.shape[0], leaf_lo.shape[1], -1).mean(-1)
    lbs = mindist_jnp(paa_q, leaf_lo, leaf_hi, n)        # [Q, L] squared
    d2 = ed2_batch_jnp(q, db_ordered)                    # [Q, N]
    neg, idx = jax.lax.top_k(-d2, k)
    return idx, jnp.sqrt(jnp.maximum(-neg, 0.0)), lbs.min(axis=1)


# ---------------------------------------------------------------------------
# host orchestration
# ---------------------------------------------------------------------------

def build_distributed(db: np.ndarray, params: DumpyParams | None = None
                      ) -> DumpyIndex:
    """Algorithm 1 with Stage 1 + histogram on the mesh.

    Uses whatever devices exist: on this container that is one CPU device
    (the code path is identical; the mesh just has size 1)."""
    params = params or DumpyParams()
    mesh = get_mesh()
    w, b = params.sax.w, params.sax.b
    db_j = jnp.asarray(db, jnp.float32)
    if mesh is not None and "data" in mesh.axis_names:
        db_j = jax.device_put(db_j, NamedSharding(mesh, P("data", None)))
    paa, sax, hist = build_step(db_j, w, b)
    # tree construction is host control flow over the (small) SAX table
    from .build import DumpyBuilder
    from .index import flatten_tree
    builder = DumpyBuilder(params)
    root, stats = builder.build_tree(np.asarray(paa), np.asarray(sax))
    flat = flatten_tree(root, b)
    return DumpyIndex(params, root, flat, np.asarray(db, np.float32),
                      np.asarray(paa), np.asarray(sax), stats)


def search_distributed(index: DumpyIndex, queries: np.ndarray, k: int,
                       nbr: int | None = None, metric: str = "ed",
                       band: int | None = None, shard_health=None):
    """Sharded kNN: a thin wrapper over the DeviceIndex search paths.

    Under a mesh with a ``data`` axis the index shards leaf-aligned over it
    and each shard runs its scan locally (per-shard top-k + all-gather
    merge); without a mesh this is the single-device program.  ``nbr`` is
    the recall/latency knob: ``None`` runs the exact windowed-pruning
    search, an integer runs the extended approximate search (paper Alg. 4 —
    the target subtree plus up to ``nbr-1`` lower-bound-ordered sibling
    leaves).  ``metric``/``band`` select the distance (``"ed"`` or banded
    ``"dtw"``, band defaulting to 10% of the length) — both paths run on
    device for either metric.  Both inherit tombstones and the in-merge
    fuzzy dedup.

    ``shard_health`` (length-``n_shards`` bools) runs degraded: dead shards
    are masked from the merge and the return becomes ``(ids, d, coverage)``
    with ``coverage`` the live-series fraction still reachable."""
    from .search_device import (exact_search_device_batch,
                                extended_search_device_batch)
    mesh = get_mesh()
    if mesh is not None and "data" not in mesh.axis_names:
        mesh = None
    if nbr is not None:
        res = extended_search_device_batch(index, queries, k,
                                           nbr=nbr, mesh=mesh,
                                           metric=metric, band=band,
                                           shard_health=shard_health)
    else:
        res = exact_search_device_batch(index, queries, k, mesh=mesh,
                                        metric=metric, band=band,
                                        shard_health=shard_health)
    if shard_health is not None:
        return res[0], res[1], res[-1]
    return res[0], res[1]


def _abstract_prep(q_batch: int, w: int, length: int):
    """ShapeDtypeStruct pytree matching ``metric.query_prep_jnp`` output
    (ED and DTW preps are shape-identical: segment interval + envelope)."""
    seg = jax.ShapeDtypeStruct((q_batch, w), jnp.float32)
    env = jax.ShapeDtypeStruct((q_batch, length), jnp.float32)
    return (seg, seg, env, env)


def lower_search_sharded(mesh, *, n_series: int = 1 << 22, length: int = 256,
                         w: int = 16, chunk: int = 8192,
                         n_leaves: int = 16384, k: int = 58,
                         q_batch: int = 64, metric=None,
                         shard_health: tuple | None = None):
    """Lower the DeviceIndex sharded windowed search on ``mesh`` with
    production shardings.  ``metric`` (a ``core.metric.Metric``; default
    ED) selects the specialization —
    ``Metric("dtw", band)`` lowers the fused masked band-DP program.
    ``shard_health`` lowers the degraded-mode specialization (dead shards
    masked before the all-gather merge).  Returns the jax ``Lowered``
    object; callers ``.compile()`` and harvest analyses."""
    from .device_index import abstract_device_index
    from .metric import ED
    from .search_device import _exact_knn_lane_sharded, _exact_knn_sharded

    met = metric or ED
    dev_abs = abstract_device_index(n_series, length, w,
                                    n_shards=mesh_shards(mesh),
                                    chunk=chunk, n_leaves=n_leaves,
                                    shard_health=shard_health, mesh=mesh)
    # the same program selection as exact_search_device_batch: DTW with a
    # per-query candidate ordering lowers the lane program
    knn = _exact_knn_lane_sharded if (met.is_dtw and met.order != "shared") \
        else _exact_knn_sharded
    # close over k/metric: pjit rejects kwargs when in_shardings is given
    search_k = lambda d, prep, q: knn(d, prep, q, k=k, metric=met)
    jitted = jax.jit(search_k,
                     in_shardings=(dev_abs.shardings(mesh), None, None))
    prep_abs = _abstract_prep(q_batch, w, length)
    q_abs = jax.ShapeDtypeStruct((q_batch, length), jnp.float32)
    return jitted.lower(dev_abs, prep_abs, q_abs)


def lower_search_dtw(mesh, *, n_series: int = 1 << 22, length: int = 256,
                     w: int = 16, chunk: int | None = None,
                     n_leaves: int = 16384, k: int = 58, q_batch: int = 64,
                     band: int | None = None, order: str = "shared"):
    """Lower the sharded *DTW* exact search (envelope bounds + the
    LB_Keogh → LB_Improved cascade + fused masked band DP) on ``mesh`` —
    the ``search_exact_dtw`` audit entries.  DTW now shares the ED-width
    layout (spans sub-block in-program, ``search_device.DTW_SUB``), so the
    span chunk defaults to the same width the ED cell lowers with,
    matching what ``exact_search_device_batch(metric="dtw")`` serves with.
    ``order`` selects the candidate ordering: ``"shared"`` lowers the span
    program, ``"perq"``/``"cluster"`` the lane-ordered program (the serving
    default — see ``core.metric.DTW_DEFAULT_ORDER``)."""
    from .metric import Metric, default_band

    return lower_search_sharded(
        mesh, n_series=n_series, length=length, w=w,
        chunk=chunk if chunk is not None else 8192,
        n_leaves=n_leaves, k=k, q_batch=q_batch,
        metric=Metric("dtw",
                      band if band is not None else default_band(length),
                      order))


def lower_search_degraded(mesh, *, n_series: int = 1 << 22,
                          length: int = 256, w: int = 16, chunk: int = 8192,
                          n_leaves: int = 16384, k: int = 58,
                          q_batch: int = 64):
    """Lower the *degraded-mode* sharded exact search: the last mesh shard
    marked dead (the canonical one-dead-shard contract the audit pins).
    ``shard_health`` is static aux data on the ``DeviceIndex``, so this is
    a separate specialization — the healthy program lowers byte-identically
    to :func:`lower_search_sharded` and keeps its own contract entry."""

    S = mesh_shards(mesh)
    health = (True,) * (S - 1) + (False,) if S > 1 else None
    return lower_search_sharded(mesh, n_series=n_series, length=length, w=w,
                                chunk=chunk, n_leaves=n_leaves, k=k,
                                q_batch=q_batch, shard_health=health)


def lower_search_extended(mesh, *, n_series: int = 1 << 22, length: int = 256,
                          w: int = 16, chunk: int = 8192,
                          n_leaves: int = 16384, k: int = 58, nbr: int = 8,
                          q_batch: int = 64):
    """Lower the DeviceIndex batched extended search (Alg. 4 descent +
    sibling schedule + shard-local leaf scan) on ``mesh`` with production
    shardings.  Returns the jax ``Lowered`` object."""
    from .device_index import abstract_device_index
    from .search_device import _extended_knn_sharded

    dev_abs = abstract_device_index(n_series, length, w,
                                    n_shards=mesh_shards(mesh),
                                    chunk=chunk, n_leaves=n_leaves,
                                    mesh=mesh)
    search_n = lambda d, prep, sq, q: _extended_knn_sharded(
        d, prep, sq, q, k=k, nbr=nbr, subtree=True, span_cap=n_leaves)
    jitted = jax.jit(search_n,
                     in_shardings=(dev_abs.shardings(mesh),
                                   None, None, None))
    prep_abs = _abstract_prep(q_batch, w, length)
    sax_abs = jax.ShapeDtypeStruct((q_batch, w), jnp.int32)
    q_abs = jax.ShapeDtypeStruct((q_batch, length), jnp.float32)
    return jitted.lower(dev_abs, prep_abs, sax_abs, q_abs)


def lower_search_approx(mesh, *, n_series: int = 1 << 22, length: int = 256,
                        w: int = 16, chunk: int = 8192,
                        n_leaves: int = 16384, k: int = 58, nbr: int = 4,
                        q_batch: int = 64, metric=None):
    """Lower the batched approximate search (vectorized root→leaf descent +
    leaf-rank scan, ``search_device._approx_knn_device``) on ``mesh`` with
    production shardings.  Returns the jax ``Lowered`` object."""
    from .device_index import abstract_device_index
    from .metric import ED
    from .search_device import _approx_knn_device

    met = metric or ED
    dev_abs = abstract_device_index(n_series, length, w,
                                    n_shards=mesh_shards(mesh),
                                    chunk=chunk, n_leaves=n_leaves,
                                    mesh=mesh)
    approx_k = lambda d, prep, sq, q: _approx_knn_device(
        d, prep, sq, q, k=k, kk=k, nbr=nbr, metric=met)
    jitted = jax.jit(approx_k,
                     in_shardings=(dev_abs.shardings(mesh),
                                   None, None, None))
    prep_abs = _abstract_prep(q_batch, w, length)
    sax_abs = jax.ShapeDtypeStruct((q_batch, w), jnp.int32)
    q_abs = jax.ShapeDtypeStruct((q_batch, length), jnp.float32)
    return jitted.lower(dev_abs, prep_abs, sax_abs, q_abs)


def lower_search_bucket(mesh, *, n_series: int = 1 << 22, length: int = 256,
                        w: int = 16, chunk: int = 8192,
                        n_leaves: int = 16384, k: int = 58, nbr: int = 8,
                        q_batch: int = 64, band: int | None = None):
    """Lower the *bucketed serving* program
    (``search_device._bucket_knn_sharded``) on ``mesh`` with production
    shardings: the coalescing front-end's per-bucket entry point where every
    per-request knob (``nbr`` budget, ED-vs-DTW metric, dead padding lanes)
    is a **traced lane array** — ``k``/``nbr`` here are the bucket-ladder
    static *maxima* (result margin and schedule width), not per-request
    values.  One contract entry per bucket shape; the recompile gate
    (``repro.analysis.recompile``) proves the warm cache key is exactly
    that shape."""
    from .device_index import abstract_device_index
    from .metric import default_band
    from .search_device import _bucket_knn_sharded

    dev_abs = abstract_device_index(n_series, length, w,
                                    n_shards=mesh_shards(mesh),
                                    chunk=chunk, n_leaves=n_leaves,
                                    mesh=mesh)
    band_eff = band if band is not None else default_band(length)
    # has_dtw=True lowers the superset (mixed-metric) variant; the pure-ED
    # sibling is the same program minus the cascade
    search_b = lambda d, pe, pd, sq, q, ln, ld: _bucket_knn_sharded(
        d, pe, pd, sq, q, ln, ld, kk=k, nbr_max=nbr, subtree=True,
        band=band_eff, span_cap=n_leaves, has_dtw=True)
    jitted = jax.jit(search_b,
                     in_shardings=(dev_abs.shardings(mesh),
                                   None, None, None, None, None, None))
    prep_abs = _abstract_prep(q_batch, w, length)
    sax_abs = jax.ShapeDtypeStruct((q_batch, w), jnp.int32)
    q_abs = jax.ShapeDtypeStruct((q_batch, length), jnp.float32)
    lane_nbr_abs = jax.ShapeDtypeStruct((q_batch,), jnp.int32)
    lane_dtw_abs = jax.ShapeDtypeStruct((q_batch,), jnp.bool_)
    return jitted.lower(dev_abs, prep_abs, prep_abs, sax_abs, q_abs,
                        lane_nbr_abs, lane_dtw_abs)


def lower_serving_head(mesh, *, vocab: int = 1 << 17, d_model: int = 256,
                       w: int = 16, n_leaves: int = 4096,
                       r_candidates: int = 128, nbr: int = 8,
                       q_batch: int = 32):
    """Lower the ``KnnSoftmaxHead`` batched retrieval program — the extended
    (Alg. 4) search at serving widths: ``r_candidates`` results per decode
    row, device-only (``rerank=False``, so no +8 re-rank slack), the
    augmented MIPS series length padded to a multiple of ``w`` exactly as
    ``KnnSoftmaxHead.__init__`` pads it."""
    length = d_model + 1 + ((-(d_model + 1)) % w)   # MIPS aug + pad, as served
    return lower_search_extended(mesh, n_series=vocab, length=length, w=w,
                                 chunk=min(8192, vocab), n_leaves=n_leaves,
                                 k=r_candidates, nbr=nbr, q_batch=q_batch)


def lower_search_oneshot(mesh, *, n_series: int = 1 << 22, length: int = 256,
                         w: int = 16, n_leaves: int = 16384, k: int = 50,
                         q_batch: int = 64):
    """Lower the one-shot LB-scan + exact-distance search (``search_step``)
    with the collection batch-sharded — the ``search_oneshot`` audit
    entry."""
    sh = NamedSharding(mesh, P(data_axes(mesh), None))
    db_abs = jax.ShapeDtypeStruct((n_series, length), jnp.float32)
    q_abs = jax.ShapeDtypeStruct((q_batch, length), jnp.float32)
    lo_abs = jax.ShapeDtypeStruct((n_leaves, w), jnp.float32)
    jitted = jax.jit(search_step, static_argnums=(4,),
                     in_shardings=(None, sh, None, None))
    return jitted.lower(q_abs, db_abs, lo_abs, lo_abs, k)


def lower_build_step(mesh, *, n_series: int = 1 << 22, length: int = 256,
                     w: int = 16, b: int = 8):
    """Lower Stage 1 + the root histogram (``build_step``) with the
    collection batch-sharded — the ``build_step`` audit entry."""
    sh = NamedSharding(mesh, P(data_axes(mesh), None))
    db_abs = jax.ShapeDtypeStruct((n_series, length), jnp.float32)
    jitted = jax.jit(build_step, static_argnums=(1, 2), in_shardings=(sh,))
    return jitted.lower(db_abs, w, b)


def lower_build_bottomup(mesh, *, n_series: int = 1 << 22, w: int = 16,
                         b: int = 8):
    """Lower the bottom-up device build's grouping program
    (``build_device._lexsort_words``: packed-word lexsort + group
    delimiting) — the device-side heart of the staged build pipeline.  The
    lexsort is global (unsharded); the program must stay collective-free."""
    from .build_device import _lexsort_words

    sax_abs = jax.ShapeDtypeStruct((n_series, w), jnp.uint8)
    return jax.jit(lambda s: _lexsort_words(s, w, b)).lower(sax_abs)
