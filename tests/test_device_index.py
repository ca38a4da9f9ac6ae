"""DeviceIndex: pytree registration, leaf-aligned shard layout, cache
invalidation on updates, and bitwise shard-count invariance of the sharded
exact search (single process; the multi-device run is exercised in
``test_distributed.py``'s subprocess test)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.build import DumpyParams
from repro.core.device_index import DeviceIndex, abstract_device_index
from repro.core.index import DumpyIndex
from repro.core.sax import SaxParams
from repro.core.search_device import (approximate_search_device_batch,
                                      exact_search_device_batch)
from repro.core.split import SplitParams
from repro.data.series import random_walks

PARAMS = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=128))
FUZZY = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=128),
                    fuzzy_f=0.15)


@pytest.fixture(scope="module")
def built():
    db = random_walks(3000, 64, seed=8)
    return db, DumpyIndex.build(db, PARAMS)


def test_pytree_roundtrip_and_jit_argument(built):
    db, idx = built
    dev = idx.device_index()
    leaves, treedef = jax.tree_util.tree_flatten(dev)
    dev2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert dev2.chunk == dev.chunk and dev2.row_bounds == dev.row_bounds
    np.testing.assert_array_equal(np.asarray(dev2.ids), np.asarray(dev.ids))

    # a DeviceIndex is a legal jit argument: aux is static, arrays trace
    @jax.jit
    def total_alive(d: DeviceIndex):
        return d.alive.sum()

    assert int(total_alive(dev)) == int(idx.alive.sum())


def test_leaf_aligned_shard_layout(built):
    db, idx = built
    S = 3
    dev = DeviceIndex.from_index(idx, n_shards=S)
    offs = idx.flat.leaf_offsets
    # shard boundaries are leaf boundaries (no leaf straddles two shards)
    assert len(dev.row_bounds) == S + 1
    assert set(dev.row_bounds) <= set(int(o) for o in offs)
    # shard content is exactly the ordered collection slices, pads marked
    ids = np.asarray(dev.ids)
    alive = np.asarray(dev.alive)
    dbs = np.asarray(dev.db)
    for s in range(S):
        r0, r1 = dev.row_bounds[s], dev.row_bounds[s + 1]
        Ts = r1 - r0
        np.testing.assert_array_equal(ids[s, :Ts], idx.flat.order[r0:r1])
        assert (ids[s, Ts:] == -1).all() and not alive[s, Ts:].any()
        np.testing.assert_array_equal(dbs[s, :Ts], idx.db_ordered[r0:r1])
    # rows balance to within one leaf pack of the ideal split
    sizes = np.diff(dev.row_bounds)
    assert sizes.max() - sizes.min() <= 2 * dev.lmax


@pytest.mark.parametrize("n_shards", [1, 2, 3])
def test_spans_start_on_128_row_tiles(n_shards):
    """Shard row counts off the tile are padded to whole 128-row tiles, so
    every span start (the end-clamped last one too) is a multiple of 128,
    as the span scan's copy of a span's ids needs; pad rows are dead."""
    idx = DumpyIndex.build(random_walks(2999, 64, seed=9), PARAMS)
    dev = DeviceIndex.from_index(idx, chunk=384, n_shards=n_shards)
    Ts = np.diff(dev.row_bounds)
    assert (Ts % 2 == 1).any() and (Ts % 128 != 0).any()
    Tp = dev.db.shape[1]
    assert Tp % 128 == 0 and dev.chunk == 384
    assert (np.asarray(dev.win_start) % 128 == 0).all()
    ids, alive = np.asarray(dev.ids), np.asarray(dev.alive)
    for s, t in enumerate(Ts):
        assert (ids[s, t:] == -1).all() and not alive[s, t:].any()
        assert (ids[s, :t] >= 0).all()


def test_inverse_order_maps_ids_to_their_rows(built):
    db, idx = built
    dev = idx.device_index()
    inv = np.asarray(dev.inv_order)
    ids_flat = np.asarray(dev.ids).reshape(-1)
    assert inv.shape == (db.shape[0],)
    assert (inv >= 0).all()
    np.testing.assert_array_equal(ids_flat[inv], np.arange(db.shape[0]))


def test_sharded_search_bitwise_invariant_to_shard_count():
    db = random_walks(1800, 64, seed=6)
    idx = DumpyIndex.build(db, FUZZY)
    assert idx.stats.n_duplicates > 0
    idx.delete(11)
    qs = random_walks(5, 64, seed=23)
    try:
        ids1, d1, _ = exact_search_device_batch(idx, qs, 8)
        for S in (2, 4):
            devS = idx.device_index(n_shards=S)
            assert devS.n_shards == S
            idsS, dS, _ = exact_search_device_batch(idx, qs, 8, dev=devS)
            np.testing.assert_array_equal(ids1, idsS)
            np.testing.assert_array_equal(d1, dS)
        assert 11 not in ids1
    finally:
        idx.alive[11] = True


def test_insert_invalidates_device_cache():
    db = random_walks(1200, 64, seed=9)
    idx = DumpyIndex.build(db, PARAMS)
    q = random_walks(1, 64, seed=77)
    exact_search_device_batch(idx, q, 3)            # populate the cache
    approximate_search_device_batch(idx, q, 3)
    assert idx._device_cache
    new_id = idx.insert(q[0])                       # rebuild → cache cleared
    assert not idx._device_cache
    ids, d, _ = exact_search_device_batch(idx, q, 3)
    assert ids[0][0] == new_id and d[0][0] == 0.0
    ids_a, _, _ = approximate_search_device_batch(idx, q, 3)
    assert ids_a[0][0] == new_id                    # routed leaf holds it


def test_delete_refreshes_alive_without_layout_rebuild(built):
    db, idx = built
    q = db[5] + 1e-3
    ids, _, _ = exact_search_device_batch(idx, q, 3)
    victim = int(ids[0][0])
    dev_before = idx._device_cache[(2048, 1, None)][0]
    try:
        idx.delete(victim)
        ids2, _, _ = exact_search_device_batch(idx, q, 3)
        assert victim not in ids2[0]
        dev_after = idx._device_cache[(2048, 1, None)][0]
        # only the tombstone mask was touched — the big arrays are shared
        assert dev_after.db is dev_before.db
        assert dev_after.ids is dev_before.ids
    finally:
        idx.alive[victim] = True
    ids3, _, _ = exact_search_device_batch(idx, q, 3)
    assert int(ids3[0][0]) == victim                # undelete visible too


def test_abstract_device_index_matches_concrete_treedef(built):
    db, idx = built
    dev = idx.device_index(n_shards=2)
    abs_dev = abstract_device_index(
        db.shape[0], idx.n, idx.w, n_shards=2, chunk=dev.chunk,
        n_leaves=dev.n_leaves, depth=dev.depth)
    # same pytree *class* structure: flatten yields the same field count and
    # every leaf is array-like (shapes differ — the abstract one is synthetic)
    c_leaves = jax.tree_util.tree_flatten(dev)[0]
    a_leaves = jax.tree_util.tree_flatten(abs_dev)[0]
    assert len(c_leaves) == len(a_leaves)
    assert all(hasattr(l, "shape") and hasattr(l, "dtype") for l in a_leaves)


def test_serving_head_tracks_deletions():
    """The serving head holds a DeviceIndex but re-resolves it through the
    index cache each batch, so a deletion between decode steps is never
    served stale (regression: a pinned snapshot kept returning dead ids)."""
    from repro.serving.knn_softmax import KnnSoftmaxHead
    rng = np.random.default_rng(3)
    W = rng.standard_normal((16, 512)).astype(np.float32)
    head = KnnSoftmaxHead(W, w=8, th=64, r_candidates=32, nbr_nodes=4)
    h = W[:, 7] + 0.01 * rng.standard_normal(16).astype(np.float32)
    cand = head.candidates_batch(h[None])
    assert 7 in cand[0]
    head.index.delete(7)
    cand2 = head.candidates_batch(h[None])
    assert 7 not in cand2[0]


def test_sibling_routing_tables_partition_subtrees(built):
    """The Alg. 4 tables: every internal node's distinct-children member
    list is begin-sorted, contiguous, and exactly partitions the node's
    subtree span; edge spans agree; every leaf knows its parent group."""
    db, idx = built
    rt = idx.routing_flat
    L = idx.flat.n_leaves
    assert rt.node_begin[0] == 0 and rt.node_end[0] == L   # root spans all
    for m in range(rt.n_nodes):
        b, e = int(rt.grp_off[m]), int(rt.grp_off[m + 1])
        gb, ge = rt.grp_begin[b:e], rt.grp_end[b:e]
        assert len(gb) >= 1
        assert gb[0] == rt.node_begin[m] and ge[-1] == rt.node_end[m]
        np.testing.assert_array_equal(gb[1:], ge[:-1])     # disjoint, sorted
    # leaf edges span exactly their leaf
    lm = rt.edge_leaf >= 0
    np.testing.assert_array_equal(rt.edge_begin[lm], rt.edge_leaf[lm])
    assert (rt.edge_nl[lm] == 1).all()
    # internal edges carry their child's node span
    im = rt.edge_child >= 0
    np.testing.assert_array_equal(rt.edge_begin[im],
                                  rt.node_begin[rt.edge_child[im]])
    np.testing.assert_array_equal(rt.edge_end[im],
                                  rt.node_end[rt.edge_child[im]])
    # every leaf has a parent group whose members contain it
    assert rt.leaf_parent.shape == (L,)
    assert (rt.leaf_parent >= 0).all()
    for lid in range(L):
        m = int(rt.leaf_parent[lid])
        assert rt.node_begin[m] <= lid < rt.node_end[m]
    # device copy pads the member tables by gmax sentinel rows
    dev = idx.device_index()
    assert dev.gmax == rt.gmax
    assert dev.grp_begin.shape[0] == rt.grp_begin.shape[0] + dev.gmax
    assert dev.leaf_bounds[0] == 0 and dev.leaf_bounds[-1] == L


def test_dedup_happens_on_device_for_serving_path():
    """The approximate (serving) path must return already-deduped ids — no
    host fixup exists on it any more."""
    db = random_walks(1500, 64, seed=2)
    idx = DumpyIndex.build(db, FUZZY)
    assert idx.stats.n_duplicates > 0
    qs = random_walks(8, 64, seed=67)
    for nbr in (1, 4):
        ids, d, _ = approximate_search_device_batch(idx, qs, 10, nbr=nbr)
        for row, drow in zip(ids, d):
            got = row[row >= 0]
            assert len(np.unique(got)) == len(got)
            assert (np.diff(drow[np.isfinite(drow)]) >= 0).all()
