"""The span loop's top-k merge, ``ops.topk_merge_cutoff``: bitwise the
sort of ``ops.topk_merge`` in every branch (nothing enters, a few insertion
rounds, the sort), and the whole exact program unchanged whether it always
sorts or never does."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import search_device as sd
from repro.core.build import DumpyParams
from repro.core.index import DumpyIndex
from repro.core.metric import resolve
from repro.core.sax import SaxParams
from repro.core.split import SplitParams
from repro.data.series import random_walks
from repro.kernels import ops

KK = 40                                       # above the round limit
Q = 6
T0 = ops.MERGE_ROUNDS                         # as shipped


def _key(x):
    """The total order ``top_k`` sorts by, in numpy (−0.0 below +0.0)."""
    b = np.asarray(x, np.float32).view(np.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _held(rng, Q, kk, n_inf=0, values=None):
    d = rng.uniform(0, 10, (Q, kk)) if values is None else \
        rng.choice(values, (Q, kk))
    d = np.sort(d.astype(np.float32), axis=1)
    if n_inf:
        d[:, kk - n_inf:] = np.inf
    d = d[np.arange(Q)[:, None], np.argsort(_key(d), axis=1, kind="stable")]
    i = rng.integers(0, 1000, (Q, kk)).astype(np.int32)
    i[np.isinf(d)] = -1
    return d, i


def _with_hits(rng, topd, C, hits):
    """``d2 [Q, C]`` with ``hits[q]`` candidates strictly below query q's
    held k-th entry and the rest at or above it (ties included)."""
    Q = topd.shape[0]
    kth = topd[:, -1:]
    d2 = kth + rng.uniform(0, 5, (Q, C)).astype(np.float32)
    d2[:, ::7] = kth                          # ties with the k-th: no hit
    for q in range(Q):
        cols = rng.choice(C, hits[q], replace=False)
        d2[q, cols] = rng.uniform(0, topd[q, -1], hits[q])
    return d2.astype(np.float32)


def _case(name, rng):
    """``(topd, topi, d2, ids)`` of one kind of span."""
    C = 256
    ids = rng.permutation(5000)[:C].astype(np.int32)
    if name == "none":
        topd, topi = _held(rng, Q, KK)
        d2 = _with_hits(rng, topd, C, [0] * Q)
    elif name in ("one", "rounds", "many"):
        topd, topi = _held(rng, Q, KK)
        top = {"one": 1, "rounds": T0, "many": KK + 5}[name]
        hits = rng.integers(0, top + 1, Q)
        hits[0] = top
        d2 = _with_hits(rng, topd, C, hits)
    elif name == "ties":
        vals = np.array([0.5, 1.0, 1.5, 2.0, 3.0], np.float32)
        topd, topi = _held(rng, Q, KK, values=vals)
        d2 = rng.choice(vals, (Q, C)).astype(np.float32)
    elif name == "signed_zero":
        vals = np.array([-0.0, 0.0, 1.0, 2.0], np.float32)
        topd, topi = _held(rng, Q, KK, values=vals)
        d2 = rng.choice(vals, (Q, C)).astype(np.float32)
        d2[:, :3] = [-0.0, 0.0, -0.0]
    elif name == "masked":
        topd, topi = _held(rng, Q, KK)
        d2 = np.full((Q, C), np.inf, np.float32)
    elif name == "held_inf":
        topd, topi = _held(rng, Q, KK, n_inf=KK - 3)
        d2 = rng.uniform(0, 20, (Q, C)).astype(np.float32)
        d2[rng.random((Q, C)) > 0.08] = np.inf
    elif name == "wide_k":                    # the span narrower than k
        C = 8
        ids = ids[:C]
        topd, topi = _held(rng, Q, KK)
        d2 = rng.uniform(0, 12, (Q, C)).astype(np.float32)
    elif name == "fuzzy_dups":                # replicas: repeated (d, id)
        topd, topi = _held(rng, Q, KK)
        base = rng.uniform(0, 12, (Q, C // 2)).astype(np.float32)
        d2 = np.concatenate([base, base], axis=1)
        ids = np.concatenate([ids[:C // 2], ids[:C // 2]])
    else:
        raise ValueError(name)
    return topd, topi, d2, ids


CASES = ("none", "one", "rounds", "many", "ties", "signed_zero", "masked",
         "held_inf", "wide_k", "fuzzy_dups")


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("rounds", ["0", "default", "k"])
@pytest.mark.parametrize("name", CASES)
def test_cutoff_merge_is_bitwise_the_sort(monkeypatch, name, rounds):
    """Each kind of span under the round limit at 0 (every span that
    changes sorts), as shipped, and at ``k`` (no span sorts)."""
    T = {"0": 0, "default": T0, "k": KK}[rounds]
    monkeypatch.setattr(ops, "MERGE_ROUNDS", T)
    rng = np.random.default_rng(CASES.index(name))
    topd, topi, d2, ids = _case(name, rng)
    idt = np.where(np.isinf(d2), -1, ids[None, :]).astype(np.int32)
    want_d, want_i = ops.topk_merge(topd, topi, d2, idt)
    got_d, got_i, m = jax.jit(
        lambda *a: ops.topk_merge_cutoff(*a))(topd, topi, d2, ids)
    np.testing.assert_array_equal(_bits(got_d), _bits(want_d))
    np.testing.assert_array_equal(got_i, want_i)
    hits = (_key(d2) < _key(topd[:, -1:])).sum(axis=1)
    assert int(m) == min(hits.max(), KK)
    expect = {"none": 0, "masked": 0, "one": 1, "rounds": T0, "many": KK}
    if name in expect:
        assert int(m) == expect[name]


def test_rounds_branch_takes_no_sort():
    """Below the limit the program's conditional holds a sort only in the
    fallback branch, and the rounds run in a loop of their own."""
    topd, topi, d2, ids = _case("rounds", np.random.default_rng(0))
    jaxpr = jax.make_jaxpr(ops.topk_merge_cutoff)(topd, topi, d2, ids)
    (eqn,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    keep, insert, sort = (str(b) for b in eqn.params["branches"])
    assert "top_k" not in keep and "top_k" not in insert
    assert "while" in insert and "top_k" in sort


def test_shard_map_keeps_the_conditional():
    """Under the shard vmap the branch follows the largest ``m`` over the
    shards, so the conditional survives batching (a batched predicate
    would run every branch, the sort included, at every span); each
    member still gets its own exact merge and its own ``m``."""
    members = [_case(n, np.random.default_rng(i))
               for i, n in enumerate(("none", "rounds", "one"))]
    args = [np.stack(x) for x in zip(*members)]
    f = jax.vmap(lambda a, b, c, d: ops.topk_merge_cutoff(a, b, c, d,
                                                           "s"),
                 axis_name="s")
    assert "cond[" in str(jax.make_jaxpr(f)(*args))
    got_d, got_i, got_m = f(*args)
    for i, x in enumerate(members):
        want_d, want_i, want_m = ops.topk_merge_cutoff(*x)
        np.testing.assert_array_equal(_bits(got_d[i]), _bits(want_d))
        np.testing.assert_array_equal(got_i[i], want_i)
        assert int(got_m[i]) == int(want_m)
    assert [int(v) for v in got_m] == [0, T0, 1]


FUZZY = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=128),
                    fuzzy_f=0.15)


@pytest.fixture(scope="module")
def fuzzy_tomb():
    idx = DumpyIndex.build(random_walks(2500, 64, seed=2), FUZZY)
    assert idx.stats.n_duplicates > 0
    for v in (3, 17, 400, 1201):
        idx.delete(v)
    return idx


@pytest.mark.parametrize("metric", ["ed", "dtw"])
@pytest.mark.parametrize("n_shards", [1, 4])
def test_exact_program_same_whether_it_sorts_or_not(monkeypatch, fuzzy_tomb,
                                                    n_shards, metric):
    """The span loop on the fuzzy + tombstone layout with the round limit
    at 0 (always sort) and at ``kk`` (never sort): every output equal,
    ``work`` included."""
    idx = fuzzy_tomb
    dev = idx.device_index(chunk=512 if metric == "dtw" else 128,
                           n_shards=n_shards)
    met = resolve(metric, 64, 4 if metric == "dtw" else None, "shared")
    qs = jnp.asarray(random_walks(8, 64, seed=23).astype(np.float32))
    prep, _ = sd._prep_batch(met, qs, idx.params.sax.w, idx.params.sax.b)
    kk = sd._result_margin(dev, 5) + 8
    outs = []
    for T in (0, kk):
        monkeypatch.setattr(ops, "MERGE_ROUNDS", T)
        knn = jax.jit(sd._exact_knn_sharded.__wrapped__,
                      static_argnames=("k", "metric"))
        outs.append(jax.device_get(knn(dev, prep, qs, k=kk, metric=met)))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(_bits(a), _bits(b))
    work = dict(zip(sd.WORK_KEYS, outs[0][4]))
    assert 0 < work["exact.spans_merged"] <= work["exact.spans_walked"]
