"""DTW fast-path tests: the LB_Keogh → LB_Improved → band-DP cascade, the
single-layout sub-blocked span loop, the per-query candidate orderings, and
the vectorized host re-rank (ISSUE 7)."""
import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.build import DumpyParams
from repro.core.index import DumpyIndex
from repro.core.device_index import DeviceIndex
from repro.core.lb import (_window_max, _window_min, dtw_envelope_batch_jnp,
                           dtw_np, dtw_np_batch, lb_improved2_batch_jnp,
                           lb_keogh2_batch_jnp)
from repro.core.sax import SaxParams
from repro.core.search import exact_search
from repro.core.search_device import exact_search_device_batch
from repro.core.split import SplitParams
from repro.data.series import random_walks

# device-path promise: no implicit host<->device transfers (conftest guard;
# the subprocess tests are unaffected — the guard is per-process)
pytestmark = pytest.mark.guard_transfers

PARAMS = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=64))
FUZZY = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=64),
                    fuzzy_f=0.15)


# ---------------------------------------------------------------------------
# LB_Improved properties
# ---------------------------------------------------------------------------

@pytest.mark.guard_transfers(False)   # eager call into jit internals
def test_window_minmax_exact():
    rng = np.random.default_rng(0)
    for n in (7, 17, 64):
        for r in (0, 1, 3, 6, n - 1):
            x = rng.normal(size=(4, n)).astype(np.float32)
            got = np.asarray(_window_max(jnp.asarray(x), r))
            ref = np.stack([[x[b, max(0, i - r):i + r + 1].max()
                             for i in range(n)] for b in range(4)])
            np.testing.assert_allclose(got, ref, rtol=0, atol=0)
            gmin = np.asarray(_window_min(jnp.asarray(x), r))
            rmin = np.stack([[x[b, max(0, i - r):i + r + 1].min()
                              for i in range(n)] for b in range(4)])
            np.testing.assert_allclose(gmin, rmin, rtol=0, atol=0)


@pytest.mark.guard_transfers(False)   # eager call into jit internals
@pytest.mark.parametrize("band", [1, 3, 6, 12])
def test_lb_improved_bounds_dtw_dominates_keogh(band):
    """On random walks: LB_Keogh² ≤ LB_Improved² ≤ DTW², at every band."""
    rng = np.random.default_rng(band)
    n, Q, m = 64, 6, 120
    xs = np.cumsum(rng.normal(size=(m, n)), axis=1).astype(np.float32)
    qs = np.cumsum(rng.normal(size=(Q, n)), axis=1).astype(np.float32)
    U, L = dtw_envelope_batch_jnp(jnp.asarray(qs), band)
    lbk2 = np.asarray(lb_keogh2_batch_jnp(jnp.asarray(xs), U, L))
    lbi2 = np.asarray(lb_improved2_batch_jnp(
        jnp.asarray(xs), jnp.asarray(qs), U, L, band))
    dtw2 = np.array([[dtw_np(q, x, band) ** 2 for x in xs] for q in qs])
    assert (lbi2 >= lbk2 - 1e-3).all()
    assert (lbi2 <= dtw2 + 1e-2).all()
    # the second pass must actually buy tightness somewhere
    assert (lbi2 > lbk2 + 1e-6).any()


@pytest.mark.guard_transfers(False)   # eager call into jit internals
def test_lb_improved_gather_layout_matches_shared():
    """The [Q, m, n] per-query layout equals per-query calls of the shared
    [m, n] layout."""
    rng = np.random.default_rng(7)
    n, Q, m, band = 64, 4, 30, 6
    cand = np.cumsum(rng.normal(size=(Q, m, n)), axis=2).astype(np.float32)
    qs = np.cumsum(rng.normal(size=(Q, n)), axis=1).astype(np.float32)
    U, L = dtw_envelope_batch_jnp(jnp.asarray(qs), band)
    got = np.asarray(lb_improved2_batch_jnp(
        jnp.asarray(cand), jnp.asarray(qs), U, L, band))
    for q in range(Q):
        ref = np.asarray(lb_improved2_batch_jnp(
            jnp.asarray(cand[q]), jnp.asarray(qs[q:q + 1]),
            U[q:q + 1], L[q:q + 1], band))[0]
        np.testing.assert_array_equal(got[q], ref)


@pytest.mark.guard_transfers(False)   # eager call into jit internals
def test_ops_lb_improved_kernel_matches_jnp():
    from repro.kernels import lb_keogh as lbk_mod, ops
    rng = np.random.default_rng(1)
    n, m, band = 64, 300, 6
    xs = np.cumsum(rng.normal(size=(m, n)), axis=1).astype(np.float32)
    q = np.cumsum(rng.normal(size=n)).astype(np.float32)
    U, L = dtw_envelope_batch_jnp(jnp.asarray(q[None, :]), band)
    ref = np.asarray(lb_improved2_batch_jnp(
        jnp.asarray(xs), jnp.asarray(q[None, :]), U, L, band))[0]
    got_k = np.asarray(lbk_mod.lb_improved(
        jnp.asarray(xs), jnp.asarray(q), U[0], L[0], r=band, interpret=True))
    got_o = np.asarray(ops.lb_improved(
        jnp.asarray(xs), jnp.asarray(q), U[0], L[0], band))
    np.testing.assert_array_equal(got_k, ref)
    np.testing.assert_array_equal(got_o, ref)


def test_dtw_np_batch_bitwise_matches_scalar():
    rng = np.random.default_rng(3)
    Q, kk, n, band = 5, 7, 48, 5
    qs = np.cumsum(rng.normal(size=(Q, n)), axis=1).astype(np.float32)
    cand = np.cumsum(rng.normal(size=(Q, kk, n)), axis=2).astype(np.float32)
    got = dtw_np_batch(qs, cand, band)
    for qi in range(Q):
        for j in range(kk):
            assert got[qi, j] == dtw_np(qs[qi], cand[qi, j], band)


# ---------------------------------------------------------------------------
# the device exact path: one layout, sub-blocking, orderings, stats
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fuzzy_tombstoned():
    db = random_walks(900, 64, seed=2)
    idx = DumpyIndex.build(db, FUZZY)
    assert idx.stats.n_duplicates > 0
    idx.delete(3)
    idx.delete(17)
    return db, idx


def _host_reference(idx, qs, k):
    out = []
    for q in qs:
        ids, d, _ = exact_search(idx, q, k, metric="dtw")
        out.append((ids, d))
    return out


def test_single_layout_serves_dtw(fuzzy_tombstoned):
    """The DTW path must not build a second DeviceIndex: after an ED and a
    DTW exact call, the cache holds exactly one (ED-width) layout."""
    db, idx = fuzzy_tombstoned
    idx._device_cache.clear()
    idx._n_device_builds = 0
    qs = random_walks(3, 64, seed=5)
    exact_search_device_batch(idx, qs, 5, metric="ed")
    exact_search_device_batch(idx, qs, 5, metric="dtw")
    assert idx._n_device_builds == 1
    assert set(idx._device_cache) == {(2048, 1, None)}


def test_subblocked_bitwise_equals_narrow_layout(fuzzy_tombstoned):
    """The sub-blocked span loop over the ED-width layout returns exactly
    what the old narrow-chunk (256) layout returns, under fuzzy replicas +
    tombstones."""
    db, idx = fuzzy_tombstoned
    qs = random_walks(5, 64, seed=6)
    ids_w, d_w, _ = exact_search_device_batch(idx, qs, 5, metric="dtw",
                                              order="shared")
    dev_narrow = DeviceIndex.from_index(idx, chunk=256, n_shards=1)
    ids_n, d_n, _ = exact_search_device_batch(idx, qs, 5, metric="dtw",
                                              order="shared", dev=dev_narrow)
    np.testing.assert_array_equal(ids_w, ids_n)
    np.testing.assert_array_equal(d_w, d_n)
    for i, (h_ids, h_d) in enumerate(_host_reference(idx, qs, 5)):
        got = ids_w[i][ids_w[i] >= 0]
        assert 3 not in got and 17 not in got
        np.testing.assert_array_equal(got, h_ids)
        np.testing.assert_array_equal(d_w[i][:len(h_d)], h_d)


def test_order_modes_agree_and_match_host(fuzzy_tombstoned):
    db, idx = fuzzy_tombstoned
    qs = random_walks(6, 64, seed=8)
    ref = _host_reference(idx, qs, 5)
    results = {}
    for order in ("shared", "perq", "cluster"):
        ids, d, vis = exact_search_device_batch(idx, qs, 5, metric="dtw",
                                                order=order)
        results[order] = (ids, d)
        assert (vis >= 1).all()
        for i, (h_ids, h_d) in enumerate(ref):
            got = ids[i][ids[i] >= 0]
            assert len(np.unique(got)) == len(got)    # fuzzy dedup held
            np.testing.assert_array_equal(got, h_ids)
            np.testing.assert_array_equal(d[i][:len(h_d)], h_d)
    np.testing.assert_array_equal(results["perq"][0], results["cluster"][0])
    np.testing.assert_array_equal(results["perq"][1], results["cluster"][1])
    np.testing.assert_array_equal(results["shared"][0], results["perq"][0])


def test_cascade_stats_accounting(fuzzy_tombstoned):
    db, idx = fuzzy_tombstoned
    qs = random_walks(6, 64, seed=9)
    for order in ("shared", "perq"):
        ids, d, vis, st = exact_search_device_batch(
            idx, qs, 5, metric="dtw", order=order, return_stats=True)
        assert st["considered"] > 0
        assert st["dp_survivors"] >= 0
        assert st["considered"] == (st["killed_lb_keogh"]
                                    + st["killed_lb_improved"]
                                    + st["dp_abandoned"]
                                    + st["dp_survivors"])
        # LB_Improved dominates LB_Keogh, so its stage must kill some of
        # what LB_Keogh let through on a real workload
        assert st["killed_lb_improved"] > 0


def test_cluster_grouping_odd_batches(fuzzy_tombstoned):
    """Batch sizes that don't split into 4/2 groups fall back gracefully."""
    db, idx = fuzzy_tombstoned
    for Q in (1, 3):
        qs = random_walks(Q, 64, seed=20 + Q)
        ids, d, _ = exact_search_device_batch(idx, qs, 4, metric="dtw",
                                              order="cluster")
        for i, (h_ids, h_d) in enumerate(_host_reference(idx, qs, 4)):
            np.testing.assert_array_equal(ids[i][ids[i] >= 0], h_ids)


def test_device_cache_coexistence(fuzzy_tombstoned):
    """ED/DTW callers and different shard counts keep distinct cache entries
    instead of evicting each other (the build counter stays put on reuse)."""
    db, idx = fuzzy_tombstoned
    idx._device_cache.clear()
    idx._n_device_builds = 0
    idx.device_index(chunk=2048, n_shards=1)
    idx.device_index(chunk=256, n_shards=1)
    idx.device_index(chunk=2048, n_shards=2)
    assert idx._n_device_builds == 3
    # hits: no rebuilds
    idx.device_index(chunk=2048, n_shards=1)
    idx.device_index(chunk=256, n_shards=1)
    assert idx._n_device_builds == 3
    assert set(idx._device_cache) == {(2048, 1, None), (256, 1, None),
                                      (2048, 2, None)}


def test_subblocked_forced_4dev_sharding():
    """Sub-blocked spans + lane-ordered program under forced 4-device
    sharding: bitwise vs single device and vs the host reference, with
    fuzzy replicas + tombstones."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import json
import numpy as np
import jax
from repro.core.build import DumpyParams
from repro.core.index import DumpyIndex
from repro.core.sax import SaxParams
from repro.core.split import SplitParams
from repro.core.search import exact_search
from repro.core.search_device import exact_search_device_batch
from repro.data.series import random_walks
from repro.distributed.sharding import make_mesh

assert len(jax.devices()) == 4
db = random_walks(800, 64, seed=2)
idx = DumpyIndex.build(db, DumpyParams(sax=SaxParams(w=8, b=8),
                                       split=SplitParams(th=64),
                                       fuzzy_f=0.15))
assert idx.stats.n_duplicates > 0
idx.delete(3); idx.delete(17)
qs = random_walks(4, 64, seed=11)
mesh = make_mesh((4,), ("data",))
for order in ("shared", "perq"):
    ids1, d1, _ = exact_search_device_batch(idx, qs, 5, metric="dtw",
                                            order=order)
    ids4, d4, _ = exact_search_device_batch(idx, qs, 5, mesh=mesh,
                                            metric="dtw", order=order)
    assert (ids1 == ids4).all() and (d1 == d4).all(), order      # bitwise
    for i, q in enumerate(qs):
        h_ids, h_d, _ = exact_search(idx, q, 5, metric="dtw")
        got = ids4[i][ids4[i] >= 0]
        assert 3 not in got and 17 not in got
        np.testing.assert_array_equal(got, h_ids)
        np.testing.assert_array_equal(d4[i][:len(h_d)], h_d)
assert (2048, 4, mesh) in idx._device_cache      # one ED-width layout only
assert not any(key[0] == 256 for key in idx._device_cache)
print(json.dumps({"ok": True}))
"""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]
