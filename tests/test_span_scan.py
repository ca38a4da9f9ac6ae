"""The span-scan kernel (``kernels/span_scan.py``) against the XLA loop it
replaces on the TPU (``search_device.span_loop``, the kernel's jnp twin):
the same walk, the same top-k, the same counters.  On the CPU the kernel
runs in Pallas's TPU interpret mode; with ``JAX_PLATFORMS=tpu`` it is
compiled (the kernel copies whole 128-lane rows, so the series here are
128 long)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import search_device as sd
from repro.core.build import DumpyParams
from repro.core.index import DumpyIndex
from repro.core.sax import SaxParams
from repro.core.search import exact_search
from repro.core.split import SplitParams
from repro.data.series import random_walks
from repro.kernels import ops
from repro.kernels import span_scan as ss

N = 128
PLAIN = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=128))
FUZZY = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=128),
                    fuzzy_f=0.15)


def _kernel(*a, **kw):
    return ss.span_scan(*a, **kw, interpret=jax.default_backend() != "tpu")


@functools.lru_cache(maxsize=None)
def _index(fuzzy: bool, tomb: bool) -> DumpyIndex:
    idx = DumpyIndex.build(random_walks(3000, N, seed=4),
                           FUZZY if fuzzy else PLAIN)
    for v in ((3, 17, 400, 1201, 2999) if tomb else ()):
        idx.delete(v)
    return idx


def _close(got, want, qs, dev):
    """``topd`` equal to rtol 1e-6 of d², or to a few ulps of the norms
    that the MXU form ``|q|² + |x|² − 2 q·x`` cancels down to d²: two
    products or norm sums of the same terms in another order differ by
    that much, and the kernel and the loop sum in their own orders."""
    scale = float((np.asarray(qs) ** 2).sum(1).max()
                  + (np.asarray(dev.db) ** 2).sum(-1).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=8 * np.finfo(np.float32).eps * scale)


def _walk_inputs(dev, qs):
    """The kernel's arguments for ``qs``, as ``_exact_knn_sharded`` builds
    them."""
    sax = _index(False, False).params.sax
    prep, _ = sd._prep_batch(sd.ED, jnp.asarray(qs), sax.w, sax.b)
    lb_g = sd._interval_lb(dev, prep[0], prep[1], dev.leaf_lo_g,
                           dev.leaf_hi_g)
    sched = jax.vmap(functools.partial(sd._walk_schedule, lb_g))(
        dev.leaf_gid, dev.win_start, dev.win_lead, dev.win_size,
        dev.edge_leaf, dev.edge_win)
    return (jnp.asarray(qs), dev.db, dev.alive, dev.ids) + tuple(sched)


# (Q, kk, fuzzy layout, tombstones, shards, chunk)
CASES = {
    "q1": (1, 18, False, False, 1, 256),
    "q7": (7, 18, False, True, 1, 256),
    "q64": (64, 18, False, False, 1, 512),
    "fuzzy_kk48": (7, 48, True, True, 1, 256),
    "fuzzy_q64_kk48": (64, 48, True, False, 1, 512),
    "clamped_lead": (7, 18, False, True, 1, 640),
    "two_shards": (7, 18, False, True, 2, 256),
    "q1_stops_early": (1, 18, False, False, 1, 128),
    # shard row counts off the 128-row tile: the layout rounds them up, so
    # every span, the end-clamped last one too, starts on a tile
    "fuzzy_two_shards_c384": (7, 18, True, True, 2, 384),
    "fuzzy_three_shards_c384": (64, 48, True, True, 3, 384),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_walks_as_the_loop(case):
    Q, kk, fuzzy, tomb, S, chunk = CASES[case]
    idx = _index(fuzzy, tomb)
    dev = idx.device_index(chunk=chunk, n_shards=S)
    qs = random_walks(Q, N, seed=11 + Q).astype(np.float32)
    args = _walk_inputs(dev, qs)
    loop = functools.partial(sd.span_loop, sd.ED, args[0], None, k=kk,
                             chunk=dev.chunk)
    want = jax.vmap(loop, axis_name=sd.SHARD_AXIS)(*args[1:])
    want = (want[0], want[1], want[2], want[4])
    got = _kernel(*args, kk=kk, chunk=dev.chunk)
    _close(got[0], want[0], qs, dev)
    for g, w in zip(got[1:3], want[1:3]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    g_work, w_work = np.asarray(got[3]), np.asarray(want[3])
    if fuzzy and S > 1:
        # merge rounds count candidates below a query's cut, and the two
        # sum d² in their own orders (``_close``): where replicas of one
        # series sit at the cut, a span can take a round more or fewer.
        # The walk itself (spans, rows, pairs, spans merged) is exact.
        np.testing.assert_array_equal(g_work[:, :4], w_work[:, :4])
        assert (np.abs(g_work[:, 4] - w_work[:, 4]) <= g_work[:, 3]).all()
    else:
        np.testing.assert_array_equal(g_work, w_work)
    work = dict(zip(sd.WORK_KEYS, g_work.sum(axis=0)))
    real = int((np.asarray(dev.win_size) > 0).sum())
    assert work["exact.merge_rounds"] >= work["exact.spans_merged"] > 0
    if case == "clamped_lead":
        assert (np.asarray(dev.win_lead) > 0).any()
    if case.endswith("_c384"):
        assert (np.diff(dev.row_bounds) % 128 != 0).all()
        assert (np.asarray(dev.win_start) % 128 == 0).all()
        if S == 3:
            assert (np.asarray(dev.win_lead)[:, -1] > 0).all()
    if case == "q1_stops_early":
        assert work["exact.spans_walked"] < real
    if case == "two_shards":
        # degraded mode: the same walk, the dead shard erased after it
        healthy = dev.with_shard_health((True, False))
        outs = []
        for fn in (None, _kernel):
            knn = jax.jit(sd._exact_knn_sharded.__wrapped__,
                          static_argnames=("k", "metric"))
            with pytest.MonkeyPatch.context() as mp:
                if fn is not None:
                    mp.setattr(ops, "span_scan", fn)
                prep = sd._prep_batch(sd.ED, args[0], idx.params.sax.w,
                                      idx.params.sax.b)[0]
                outs.append(jax.device_get(knn(healthy, prep, args[0],
                                               k=kk)))
        # sqrt halves the relative gap, so the bound on d² holds for d
        _close(outs[1][0], outs[0][0], qs, dev)
        for a, b in zip(outs[0][1:], outs[1][1:]):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("fuzzy", [False, True])
def test_exact_search_on_the_kernel_is_the_host_search(monkeypatch, fuzzy):
    """The whole exact path with the kernel walking the spans: bitwise the
    host's ``exact_search``, fuzzy duplicates and tombstones included."""
    idx = _index(fuzzy, True)
    monkeypatch.setattr(ops, "span_scan", _kernel)
    # a fresh jit, so that no program traced with the loop is reused
    monkeypatch.setattr(sd, "_exact_knn_sharded",
                        jax.jit(sd._exact_knn_sharded.__wrapped__,
                                static_argnames=("k", "metric")))
    qs = random_walks(5, N, seed=31).astype(np.float32)
    ids, d, _ = sd.exact_search_device_batch(idx, qs, 10, chunk=256)
    for q in range(len(qs)):
        h_ids, h_d = exact_search(idx, qs[q], 10)[:2]
        np.testing.assert_array_equal(ids[q], np.asarray(h_ids))
        np.testing.assert_array_equal(d[q], np.asarray(h_d, np.float32))


def test_query_groups_keep_each_querys_walk(monkeypatch):
    """A batch past ``MAX_BLOCK`` is walked in query groups: every query's
    top-k and visits are those of the whole batch's walk (a query stops
    merging at its own bound whatever the others do)."""
    idx = _index(False, True)
    dev = idx.device_index(chunk=256)
    qs = random_walks(20, N, seed=41).astype(np.float32)
    args = _walk_inputs(dev, qs)
    want = _kernel(*args, kk=18, chunk=dev.chunk)
    monkeypatch.setattr(ss, "MAX_BLOCK", 8 * dev.chunk)     # groups of 8
    ss.span_scan.clear_cache()
    jaxpr = str(jax.make_jaxpr(functools.partial(_kernel, kk=18,
                                                 chunk=dev.chunk))(*args))
    assert "f32[8,128]" in jaxpr and "f32[4,128]" in jaxpr    # 8 + 8 + 4
    got = _kernel(*args, kk=18, chunk=dev.chunk)
    ss.span_scan.clear_cache()
    for g, w in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    g_work, w_work = (dict(zip(sd.WORK_KEYS, np.asarray(x[3]).sum(0)))
                      for x in (got, want))
    assert g_work["exact.pairs_needed"] == w_work["exact.pairs_needed"]
    assert g_work["exact.spans_walked"] >= w_work["exact.spans_walked"]
