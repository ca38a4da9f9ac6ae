"""The plain reference against an independent numpy brute force, and the
on-device Rand generator against the program's ``random_walks``."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, reference


def _numpy_knn(db, qs, k):
    d = np.sqrt(((qs[:, None, :].astype(np.float64)
                  - db[None].astype(np.float64)) ** 2).sum(-1))
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, 1)


def test_knn_equals_numpy_brute_force():
    db = np.asarray(data.walks(11, 0, 4096, 64))
    qs = np.asarray(data.walks(11, 1, 12, 64))
    ids, d = reference.knn(jnp.asarray(db), qs, 7)
    ref_i, ref_d = _numpy_knn(db, qs, 7)
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-6)


def test_distances_of_given_ids():
    db = np.asarray(data.walks(12, 0, 2048, 64))
    qs = np.asarray(data.walks(12, 1, 10, 64))
    ids = np.random.default_rng(0).choice(2048, (10, 5))
    ids[3, 2:] = -1
    got = reference.distances(jnp.asarray(db), qs, ids)
    want = np.sqrt(((qs[:, None, :].astype(np.float64)
                     - db[np.maximum(ids, 0)].astype(np.float64)) ** 2).sum(-1))
    np.testing.assert_allclose(got[ids >= 0], want[ids >= 0], rtol=1e-6)
    assert np.isinf(got[3, 2:]).all()


def test_bf16_control_is_off_by_far_more_than_f32_rounding():
    db = jnp.asarray(data.walks(13, 0, 4096, 64))
    qs = np.asarray(data.walks(13, 1, 16, 64))
    _, d32 = reference.knn(db, qs, 5)
    _, d16 = reference.knn(db, qs, 5, dtype=jnp.bfloat16)
    assert np.max(np.abs(d16 - d32) / d32) > 1e-4


def test_generator_is_rand():
    from repro.data.series import random_walks
    x = np.asarray(data.walks(2**40 + 3, 0, 4096, 256), np.float64)
    np.testing.assert_allclose(x.mean(1), 0.0, atol=1e-5)
    np.testing.assert_allclose(x.std(1), 1.0, atol=1e-4)
    ours = np.diff(x, axis=1)
    # increments of a z-normalised walk: N(0, 1) steps over the walk's
    # standard deviation, so their spread matches the program's generator
    theirs = np.diff(random_walks(4096, 256, seed=0).astype(np.float64), axis=1)
    assert abs(ours.mean()) < 1e-3
    assert ours.std() == pytest.approx(theirs.std(), rel=0.05)
    assert abs(np.mean((ours - ours.mean()) ** 3) / ours.std() ** 3) < 0.1
    # same seed, same bits; another seed or stream, other bits
    np.testing.assert_array_equal(np.asarray(data.walks(5, 0, 1024, 64)),
                                  np.asarray(data.walks(5, 0, 1024, 64)))
    assert not np.array_equal(np.asarray(data.walks(5, 0, 1024, 64)),
                              np.asarray(data.walks(5, 1, 1024, 64)))
