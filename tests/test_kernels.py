"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.lb_isax import lb_isax
from repro.kernels.pairwise_l2 import pairwise_l2
from repro.kernels.sax_encode import sax_encode

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("B", [1, 7, 256, 300])
@pytest.mark.parametrize("n,w", [(64, 8), (128, 16), (256, 16), (96, 12)])
@pytest.mark.parametrize("b", [4, 8])
def test_sax_encode_sweep(B, n, w, b):
    x = RNG.standard_normal((B, n)).astype(np.float32)
    paa, sax = sax_encode(jnp.asarray(x), w=w, b=b, interpret=True)
    paa_r, sax_r = ref.sax_encode_ref(jnp.asarray(x), w, b)
    np.testing.assert_allclose(np.asarray(paa), np.asarray(paa_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(sax), np.asarray(sax_r))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_sax_encode_dtypes(dtype):
    x = RNG.standard_normal((33, 64)).astype(dtype)
    paa, sax = sax_encode(jnp.asarray(x), w=8, b=8, interpret=True)
    paa_r, sax_r = ref.sax_encode_ref(jnp.asarray(x), 8, 8)
    np.testing.assert_array_equal(np.asarray(sax), np.asarray(sax_r))


@pytest.mark.parametrize("Q,X,n", [(1, 1, 64), (17, 333, 96), (128, 128, 128),
                                   (5, 1000, 256), (130, 50, 320)])
def test_pairwise_l2_sweep(Q, X, n):
    q = RNG.standard_normal((Q, n)).astype(np.float32)
    x = RNG.standard_normal((X, n)).astype(np.float32)
    got = pairwise_l2(jnp.asarray(q), jnp.asarray(x), interpret=True)
    want = ref.pairwise_l2_ref(jnp.asarray(q), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-2, rtol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_pairwise_l2_dtypes(dtype):
    q = RNG.standard_normal((9, 64)).astype(dtype)
    x = RNG.standard_normal((70, 64)).astype(dtype)
    got = pairwise_l2(jnp.asarray(q), jnp.asarray(x), interpret=True)
    want = ref.pairwise_l2_ref(jnp.asarray(q), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-2, rtol=1e-3)


@pytest.mark.parametrize("Q,L,w,n", [(1, 1, 8, 64), (9, 77, 16, 128),
                                     (8, 512, 16, 256), (3, 1500, 8, 64)])
def test_lb_isax_sweep(Q, L, w, n):
    lo = RNG.standard_normal((L, w)).astype(np.float32)
    hi = lo + np.abs(RNG.standard_normal((L, w))).astype(np.float32)
    pq = RNG.standard_normal((Q, w)).astype(np.float32)
    got = lb_isax(jnp.asarray(pq), jnp.asarray(lo), jnp.asarray(hi), n=n,
                  interpret=True)
    want = ref.lb_isax_ref(jnp.asarray(pq), jnp.asarray(lo), jnp.asarray(hi), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-5)


def test_ops_wrappers_roundtrip():
    """Public ops API end-to-end on CPU (interpret auto-selected)."""
    x = RNG.standard_normal((100, 64)).astype(np.float32)
    paa, sax = ops.sax_encode(jnp.asarray(x), 8, 8)
    assert paa.shape == (100, 8) and sax.shape == (100, 8)
    d = ops.pairwise_l2(jnp.asarray(x[:5]), jnp.asarray(x))
    assert np.allclose(np.asarray(d)[np.arange(5), np.arange(5)], 0.0, atol=1e-3)
    ids, d2 = ops.knn_from_leaves(jnp.asarray(x[0]), jnp.asarray(x), 3)
    assert int(ids[0]) == 0


@pytest.mark.parametrize("B,n", [(1, 64), (100, 64), (300, 128), (257, 96)])
def test_lb_keogh_sweep(B, n):
    from repro.kernels.lb_keogh import lb_keogh
    x = RNG.standard_normal((B, n)).astype(np.float32)
    q = RNG.standard_normal(n).astype(np.float32)
    from repro.core.lb import dtw_envelope_np
    U, L = dtw_envelope_np(q, max(1, n // 10))
    got = lb_keogh(jnp.asarray(x), jnp.asarray(U), jnp.asarray(L),
                   interpret=True)
    want = ref.lb_keogh_ref(jnp.asarray(x), jnp.asarray(U), jnp.asarray(L))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-5)


def test_lb_keogh_lower_bounds_dtw():
    """LB_Keogh(q, x) ≤ DTW(q, x) — the pruning invariant."""
    from repro.core.lb import dtw_envelope_np, dtw_np
    from repro.kernels.lb_keogh import lb_keogh
    n, band = 64, 6
    q = RNG.standard_normal(n).astype(np.float32)
    xs = RNG.standard_normal((40, n)).astype(np.float32)
    U, L = dtw_envelope_np(q, band)
    lb2 = np.asarray(lb_keogh(jnp.asarray(xs), jnp.asarray(U), jnp.asarray(L),
                              interpret=True))
    for i, x in enumerate(xs):
        assert np.sqrt(lb2[i]) <= dtw_np(q, x, band) + 1e-3


@pytest.mark.parametrize("Q,L,w,n", [(1, 1, 8, 64), (9, 77, 16, 128),
                                     (3, 600, 8, 64)])
def test_lb_paa_interval_sweep(Q, L, w, n):
    """The interval-MINDIST kernel vs the fused-jnp oracle, and its
    degenerate case vs the historical ED kernel (bitwise)."""
    from repro.core.lb import lb_interval_jnp, mindist_jnp
    from repro.kernels.lb_isax import lb_paa_interval
    lo = RNG.standard_normal((L, w)).astype(np.float32)
    hi = lo + np.abs(RNG.standard_normal((L, w))).astype(np.float32)
    sl = RNG.standard_normal((Q, w)).astype(np.float32)
    sh = sl + np.abs(RNG.standard_normal((Q, w))).astype(np.float32)
    got = lb_paa_interval(jnp.asarray(sl), jnp.asarray(sh), jnp.asarray(lo),
                          jnp.asarray(hi), n=n, interpret=True)
    want = lb_interval_jnp(jnp.asarray(sl), jnp.asarray(sh), jnp.asarray(lo),
                           jnp.asarray(hi), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-5)
    deg = lb_isax(jnp.asarray(sl), jnp.asarray(lo), jnp.asarray(hi), n=n,
                  interpret=True)
    degw = mindist_jnp(jnp.asarray(sl), jnp.asarray(lo), jnp.asarray(hi), n)
    np.testing.assert_array_equal(np.asarray(deg), np.asarray(degw))


@pytest.mark.parametrize("Q,m,n,r,bm", [(1, 1, 64, 6, 8), (3, 50, 64, 6, 16),
                                        (2, 20, 96, 10, 32)])
def test_dtw_band_kernel_sweep(Q, m, n, r, bm):
    """The Pallas masked band-DP kernel vs the host DTW reference, plus the
    mask/cutoff semantics (masked lanes +inf, survivors exact)."""
    from repro.core.lb import dtw_np
    from repro.kernels.dtw_band import dtw_band
    qs = RNG.standard_normal((Q, n)).astype(np.float32)
    xs = RNG.standard_normal((m, n)).astype(np.float32)
    mask = jnp.ones((Q, m), bool)
    cut = jnp.full((Q,), jnp.inf)
    d2 = np.asarray(dtw_band(jnp.asarray(qs), jnp.asarray(xs), mask, cut,
                             r=r, block_m=bm, interpret=True))
    ref = np.array([[dtw_np(q, x, r) for x in xs] for q in qs])
    np.testing.assert_allclose(np.sqrt(d2), ref, atol=1e-3, rtol=1e-4)
    # masked lanes skip and report +inf
    mask2 = mask.at[:, ::2].set(False)
    d2m = np.asarray(dtw_band(jnp.asarray(qs), jnp.asarray(xs), mask2, cut,
                              r=r, block_m=bm, interpret=True))
    assert np.isinf(d2m[:, ::2]).all()
    np.testing.assert_array_equal(d2m[:, 1::2], d2[:, 1::2])
    # cutoff abandon never loses a below-cutoff candidate
    cut2 = jnp.asarray(np.quantile(ref ** 2, 0.3, axis=1).astype(np.float32))
    d2c = np.asarray(dtw_band(jnp.asarray(qs), jnp.asarray(xs), mask, cut2,
                              r=r, block_m=bm, interpret=True))
    below = ref ** 2 < np.asarray(cut2)[:, None] - 1e-3
    np.testing.assert_allclose(d2c[below], (ref ** 2)[below],
                               atol=1e-2, rtol=1e-4)


def test_ops_dtw_band_cpu_fallback_matches_kernel():
    """Off-TPU ``ops.dtw_band`` routes to the jnp anti-diagonal twin; both
    compute the same f32 cell recurrence, so they agree bitwise — masked
    lanes, cutoff abandons and the TPU tile width (128 lanes) included."""
    from repro.kernels.dtw_band import dtw_band as pallas_dtw
    qs = jnp.asarray(RNG.standard_normal((9, 64)).astype(np.float32))
    xs = jnp.asarray(RNG.standard_normal((130, 64)).astype(np.float32))
    mask = jnp.asarray(RNG.random((9, 130)) < 0.7)
    full = np.asarray(ops.dtw_band(qs, xs, jnp.ones((9, 130), bool),
                                   jnp.full((9,), jnp.inf), 6))
    cut = jnp.asarray(np.quantile(full, 0.3, axis=1).astype(np.float32))
    got = np.asarray(ops.dtw_band(qs, xs, mask, cut, 6))
    want = np.asarray(pallas_dtw(qs, xs, mask, cut, r=6, interpret=True))
    np.testing.assert_array_equal(got, want)
