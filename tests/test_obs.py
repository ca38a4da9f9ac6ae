"""The program's spans and counters (``repro.obs``): nesting, self time,
the ring's bound, window clipping, the exact path's spans on the profiler's
timeline, and the span loop's work counters against a host replay."""
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import search_device as sd
from repro.core.build import DumpyParams
from repro.core.index import DumpyIndex
from repro.core.sax import SaxParams
from repro.core.split import SplitParams
from repro.data.series import random_walks

EXACT = ("dumpy.exact.prep", "dumpy.exact.launch", "dumpy.exact.wait",
         "dumpy.exact.finalize")


@pytest.fixture(scope="module")
def index():
    db = random_walks(4000, 64, seed=4)
    return DumpyIndex.build(db, DumpyParams(sax=SaxParams(w=8, b=8),
                                            split=SplitParams(th=64)))


def _since(m):
    return ([s for s in obs.spans() if s.sid > m],
            [c for c in obs.counters() if c.span > m])


def _root(by_sid, s):
    while s.parent in by_sid:
        s = by_sid[s.parent]
    return s


def test_nesting_parents_and_shared_call_id():
    m = obs.mark()
    with obs.span("outer", call=7) as a:
        with obs.span("mid") as b:
            with obs.span("inner") as c:
                obs.count("n", 3)
        with obs.span("mid2") as d:
            pass
    with obs.span("other", call=8) as e:
        pass
    got, cnt = _since(m)
    by = {s.sid: s for s in got}
    assert (by[a].parent, by[b].parent, by[c].parent, by[d].parent) == \
        (0, a, b, a)
    assert by[e].parent == 0
    assert [_root(by, by[x]).attrs["call"] for x in (a, b, c, d)] == [7] * 4
    assert _root(by, by[e]).attrs["call"] == 8
    assert a < b < c < d < e                        # ids increase
    assert by[a].t0 <= by[b].t0 <= by[c].t0 <= by[c].t1 <= by[b].t1 \
        <= by[d].t0 <= by[d].t1 <= by[a].t1
    assert [(x.name, x.n, x.span) for x in cnt] == [("n", 3, c)]


def test_span_records_on_exception():
    m = obs.mark()
    with pytest.raises(ValueError):
        with obs.span("fails"):
            raise ValueError("x")
    with obs.span("after") as s:
        pass
    got, _ = _since(m)
    assert [x.name for x in got] == ["fails", "after"]
    assert got[1].parent == 0 and got[1].sid == s


def test_self_time_of_hand_built_spans():
    S = obs.Span
    spans = [S("call", 1, 0, 0, 100, None),
             S("prep", 2, 1, 10, 30, None),
             S("dev", 3, 2, 12, 20, None),
             S("prep", 4, 1, 40, 50, None),
             S("call", 5, 0, 200, 260, None),
             S("prep", 6, 5, 210, 215, None)]
    assert obs.self_time(spans, "call") == [100 - 20 - 10, 60 - 5]
    assert obs.self_time(spans, "prep") == [20 - 8, 10, 5]
    assert obs.self_time(spans, "dev") == [8]
    assert obs.self_time(spans, "none") == []


def test_rings_stay_bounded():
    for i in range(obs.CAPACITY + 50):
        with obs.span("fill"):
            obs.count("fill", i)
    sp, cn = obs.spans(), obs.counters()
    assert len(sp) == len(cn) == obs.CAPACITY
    assert cn[-1].n == obs.CAPACITY + 49 and cn[0].n == 50
    assert sp[-1].sid - sp[0].sid == obs.CAPACITY - 1


def test_clip_keeps_what_lies_in_the_window():
    S, C = obs.Span, obs.Count
    spans = [S("a", 1, 0, 0, 10, None), S("b", 2, 0, 10, 20, None),
             S("c", 3, 0, 15, 30, None), S("d", 4, 0, 20, 20, None)]
    counts = [C("x", 1, 9, 0), C("x", 2, 10, 0), C("x", 4, 20, 0),
              C("x", 8, 21, 0)]
    assert [s.name for s in obs.clip(spans, 10, 20)] == ["b", "d"]
    assert [c.n for c in obs.clip(counts, 10, 20)] == [2, 4]


def test_compiles_are_spans_under_the_open_span():
    n0, m = obs.compiles(), obs.mark()
    with obs.span("outer") as a:
        jax.jit(lambda x: x * 3 - 1)(np.arange(5.0, dtype=np.float32))
    got, _ = _since(m)
    comp = [s for s in got if s.name == obs.COMPILE]
    assert obs.compiles() - n0 == len(comp) >= 1
    assert all(s.parent == a and s.t1 >= s.t0 for s in comp)
    assert any("lambda" in s.attrs["fun"] for s in comp)


def test_exact_call_spans_nest_and_share_the_device_timeline(index,
                                                             tmp_path):
    qs = random_walks(8, 64, seed=5)
    sd.exact_search_device_batch(index, qs, 5, chunk=128)     # compile
    m = obs.mark()
    jax.profiler.start_trace(str(tmp_path))
    try:
        sd.exact_search_device_batch(index, qs, 5, chunk=128)
    finally:
        jax.profiler.stop_trace()

    got, _ = _since(m)
    call = [s for s in got if s.name == "dumpy.exact.call"]
    assert len(call) == 1 and call[0].attrs["Q"] == 8
    assert call[0].attrs["k"] == 5 and call[0].attrs["chunk"] == 128
    # ED walks its spans in the kernel on a TPU, in the XLA loop elsewhere
    assert call[0].attrs["scan"] == (
        "kernel" if sd.ops.span_scan_kernel(64) else "loop")
    kids = sorted((s for s in got if s.parent == call[0].sid),
                  key=lambda s: s.t0)
    assert [s.name for s in kids] == list(EXACT)

    pb = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                   recursive=True)
    pd = jax.profiler.ProfileData.from_file(pb[-1])
    host, ops = {}, []
    for plane in pd.planes:
        for ln in plane.lines:
            for e in ln.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith("dumpy.exact."):
                    host[e.name] = iv
                elif ln.name.startswith("tf_XLAPjRtCpuClient") \
                        and e.duration_ns > 0:
                    ops.append(iv)
    assert set(host) == {"dumpy.exact.call", *EXACT}
    c0, c1 = host["dumpy.exact.call"]
    prev = c0
    for name in EXACT:                      # nested in the call, in order
        s0, s1 = host[name]
        assert prev <= s0 <= s1 <= c1
        prev = s1
    # the program's operations run on the same clock, between the launch
    # and the end of the wait
    l0, w1 = host["dumpy.exact.launch"][0], host["dumpy.exact.wait"][1]
    inside = [o for o in ops if l0 <= o[0] and o[1] <= w1]
    assert inside


def _replay(index, dev, qs, kk):
    """The span loop of ``_exact_knn_sharded`` on the host, per shard, from
    the program's own schedule: ``(spans walked, live rows, live rows ×
    active queries, spans at which a candidate entered a running top-k,
    merge rounds, visited [Q])`` summed over shards."""
    sax = index.params.sax
    prep, _ = sd._prep_batch(sd.ED, jax.numpy.asarray(qs), sax.w, sax.b)
    lb_g = np.asarray(sd._interval_lb(dev, prep[0], prep[1],
                                      dev.leaf_lo_g, dev.leaf_hi_g))
    Q = qs.shape[0]
    walked = rows = pairs = merged = rounds = 0
    vis = np.zeros(Q, np.int64)
    for s in range(dev.db.shape[0]):
        gid = np.asarray(dev.leaf_gid[s])
        lbq = np.where(gid[None, :] >= 0, lb_g[:, np.maximum(gid, 0)],
                       np.inf)
        W = dev.win_size.shape[1]
        win_lb = np.full((Q, W), np.inf, np.float32)
        e_leaf, e_win = np.asarray(dev.edge_leaf[s]), np.asarray(
            dev.edge_win[s])
        for e in range(len(e_leaf)):
            win_lb[:, e_win[e]] = np.minimum(win_lb[:, e_win[e]],
                                             lbq[:, e_leaf[e]])
        order = np.argsort(win_lb.min(axis=0), kind="stable")
        start = np.asarray(dev.win_start[s])[order]
        lead = np.asarray(dev.win_lead[s])[order]
        size = np.asarray(dev.win_size[s])[order]
        win_lb = win_lb[:, order]
        suffix = np.minimum.accumulate(win_lb[:, ::-1], axis=1)[:, ::-1]
        db_s = np.asarray(dev.db[s], np.float64)
        topd = np.full((Q, kk), np.inf)
        i = 0
        while i < W and (suffix[:, i] < topd[:, -1]).any():
            qact = win_lb[:, i] < topd[:, -1]
            r0 = start[i] + lead[i]
            slab = db_s[r0:r0 + size[i]]
            d2 = ((qs[:, None, :] - slab[None]) ** 2).sum(-1)
            d2 = np.where(qact[:, None], d2, np.inf)
            hits = (d2 < topd[:, -1:]).sum(axis=1)
            merged += bool(hits.any())
            rounds += min(int(hits.max()), kk)
            topd = np.sort(np.concatenate([topd, d2], 1), 1)[:, :kk]
            walked += 1
            rows += int(size[i])
            pairs += int(size[i]) * int(qact.sum())
            vis += qact
            i += 1
    return walked, rows, pairs, merged, rounds, vis


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("k,Q", [(3, 2), (4000, 8)])
def test_loop_counters_equal_a_host_replay(index, n_shards, k, Q):
    """``k`` 3 prunes; ``k`` 4000 (every row) walks every real span.  Four
    shards run as the vmapped shard axis on the one device."""
    qs = random_walks(Q, 64, seed=9).astype(np.float32)
    dev = index.device_index(chunk=128, n_shards=n_shards)
    m = obs.mark()
    _, _, visited = sd.exact_search_device_batch(index, qs, k, dev=dev)
    _, cnt = _since(m)
    got = {c.name: c.n for c in cnt}
    assert set(got) == set(sd.WORK_KEYS)
    walked, rows, pairs, merged, rounds, vis = _replay(index, dev, qs, k + 8)
    assert (got["exact.spans_walked"], got["exact.rows_live"],
            got["exact.pairs_needed"], got["exact.spans_merged"],
            got["exact.merge_rounds"]) == \
        (walked, rows, pairs, merged, rounds)
    np.testing.assert_array_equal(visited, vis)
    real = int((np.asarray(dev.win_size) > 0).sum())
    if k >= 4000:
        assert walked == real and rows == 4000 and pairs == Q * rows
        assert merged == walked              # k-th best stays +inf
    elif n_shards == 1:
        assert walked < real                 # the test exercises pruning
        assert 0 < merged < walked           # and spans that change nothing


def test_lane_program_records_no_loop_counters(index):
    qs = random_walks(4, 64, seed=3)
    m = obs.mark()
    sd.exact_search_device_batch(index, qs, 3, metric="dtw", band=4,
                                 order="cluster")
    got, cnt = _since(m)
    assert cnt == []
    assert "dumpy.exact.wait" in {s.name for s in got}


def test_build_stages_and_upload_are_spans():
    m = obs.mark()
    idx = DumpyIndex.build(random_walks(1500, 64, seed=1),
                           DumpyParams(sax=SaxParams(w=8, b=8),
                                       split=SplitParams(th=64)),
                           backend="device")
    idx.device_index()
    idx.device_index()                        # a cache hit: no span
    got, _ = _since(m)
    by = {s.sid: s for s in got}
    build = [s for s in got if s.name == "dumpy.build"]
    assert len(build) == 1 and build[0].attrs == {"backend": "device"}
    stages = sorted((s for s in got if s.parent == build[0].sid
                     and s.name != obs.COMPILE), key=lambda s: s.t0)
    assert [s.name for s in stages] == ["dumpy.build.encode",
                                        "dumpy.build.split",
                                        "dumpy.build.layout"]
    up = [s for s in got if s.name == "dumpy.device_index"]
    assert len(up) == 1 and up[0].parent == 0
    assert build[0].t1 <= up[0].t0
    assert all(_root(by, s).name in ("dumpy.build", "dumpy.device_index")
               for s in got if s.name == obs.COMPILE and s.parent)
