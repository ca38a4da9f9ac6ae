"""Distributed-runtime tests: sharded Dumpy build/search (the 4-device
bitwise parity runs in a subprocess, the only place a multi-device mesh can
exist under pytest), sharding-rule resolution, and the kNN-softmax head."""
import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp

from repro.core.baselines.brute import brute_force_knn
from repro.core.build import DumpyParams
from repro.core.distributed import (build_distributed, build_step,
                                    search_distributed, search_step)
from repro.core.index import DumpyIndex
from repro.core.sax import SaxParams
from repro.core.split import SplitParams
from repro.data.series import random_walks
from repro.distributed.sharding import (DEFAULT_RULES, logical_rules,
                                        logical_spec, make_mesh, shard)

PARAMS = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=128))


def test_build_step_matches_host_encoder():
    db = random_walks(512, 64, seed=0)
    paa, sax, hist = build_step(jnp.asarray(db), 8, 8)
    from repro.core.sax import sax_encode_np
    paa_h, sax_h = sax_encode_np(db, PARAMS.sax)
    np.testing.assert_allclose(np.asarray(paa), paa_h, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(sax), sax_h)
    assert int(jnp.sum(hist)) == 512              # histogram covers all series


def test_distributed_build_and_search_equal_host_path():
    db = random_walks(3000, 64, seed=1)
    idx = build_distributed(db, PARAMS)
    qs = random_walks(4, 64, seed=99)
    ids, d = search_distributed(idx, qs, k=5)
    for i, q in enumerate(qs):
        gt_ids, gt_d = brute_force_knn(db, q, 5)
        np.testing.assert_allclose(np.sort(d[i]), np.sort(gt_d), atol=1e-3)


def test_search_step_returns_per_query_min_lb():
    """Regression: the per-query pruning statistic is [Q]-shaped (it used to
    be truncated to k entries) and lower-bounds each query's true nearest
    distance."""
    db = random_walks(512, 64, seed=4)
    idx = DumpyIndex.build(db, PARAMS)
    q = random_walks(7, 64, seed=5)
    ids, d, lbs = search_step(jnp.asarray(q), jnp.asarray(idx.db_ordered),
                              jnp.asarray(idx.flat.leaf_lo),
                              jnp.asarray(idx.flat.leaf_hi), 3)
    assert lbs.shape == (7,)                        # [Q], not [k]
    assert ids.shape == (7, 3) and d.shape == (7, 3)
    # lbs is squared MINDIST; its sqrt bounds the true nearest distance
    assert np.all(np.sqrt(np.asarray(lbs)) <= np.asarray(d[:, 0]) + 1e-4)


def test_sharded_search_multidevice_bitwise_parity_subprocess():
    """The DeviceIndex sharded exact *and extended* searches on a forced
    4-device host mesh must be bitwise-identical to their host references —
    including fuzzy duplicates (deduped in the device merge) and
    tombstones."""
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
from repro.core.search import exact_search, extended_search
from repro.core.search_device import (exact_search_device_batch,
                                      extended_search_device_batch)
from repro.data.series import random_walks
from repro.distributed.sharding import make_mesh

assert len(jax.devices()) == 4
db = random_walks(1200, 64, seed=2)
idx = DumpyIndex.build(db, DumpyParams(sax=SaxParams(w=8, b=8),
                                       split=SplitParams(th=64),
                                       fuzzy_f=0.15))
assert idx.stats.n_duplicates > 0
idx.delete(3); idx.delete(17)
qs = random_walks(6, 64, seed=11)
mesh = make_mesh((4,), ("data",))
ids1, d1, _ = exact_search_device_batch(idx, qs, 5)             # 1 shard
ids4, d4, _ = exact_search_device_batch(idx, qs, 5, mesh=mesh)  # 4 shards
dev = idx._device_cache[(2048, 4, mesh)][0]
assert len(dev.db.sharding.device_set) == 4, dev.db.sharding
assert (ids1 == ids4).all() and (d1 == d4).all()                # bitwise
for i, q in enumerate(qs):
    h_ids, h_d, _ = exact_search(idx, q, 5)
    got = ids4[i][ids4[i] >= 0]
    assert len(np.unique(got)) == len(got)          # dedup in the merge
    assert 3 not in got and 17 not in got           # tombstones respected
    np.testing.assert_array_equal(got, h_ids)
    np.testing.assert_array_equal(d4[i][:len(h_d)], h_d)
for nbr in (1, 4):
    e1, ed1, _ = extended_search_device_batch(idx, qs, 5, nbr=nbr)
    e4, ed4, _ = extended_search_device_batch(idx, qs, 5, nbr=nbr, mesh=mesh)
    assert (e1 == e4).all() and (ed1 == ed4).all()              # bitwise
    for i, q in enumerate(qs):
        h_ids, h_d, _ = extended_search(idx, q, 5, nbr)
        got = e4[i][e4[i] >= 0]
        np.testing.assert_array_equal(got, h_ids)
        np.testing.assert_array_equal(ed4[i][:len(h_d)], h_d)
print(json.dumps({"ok": True, "n_dev": len(jax.devices())}))
"""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["n_dev"] == 4


def test_sharding_rules_resolution_no_mesh_is_noop():
    x = jnp.ones((4, 8))
    assert shard(x, "batch", "embed") is x          # no mesh → identity


def test_sharding_rules_drop_conflicts_and_missing_axes():
    mesh = make_mesh((1,), ("model",))
    with logical_rules(mesh, DEFAULT_RULES):
        spec = logical_spec(("heads", "mlp"))       # both map to 'model'
        # second use of the same mesh axis must be dropped
        assert spec[0] == "model" and spec[1] is None
        spec2 = logical_spec(("batch",))            # pod/data not in mesh
        assert spec2[0] is None


def test_knn_softmax_mips_reduction_exactness():
    """The augmented-coordinate reduction must make brute-force L2 order
    equal inner-product order."""
    rng = np.random.default_rng(0)
    d, vocab = 16, 400
    W = rng.standard_normal((d, vocab)).astype(np.float32)
    W *= rng.uniform(0.5, 2.0, vocab)[None, :]     # spread the norms
    rows = W.T
    n2 = (rows ** 2).sum(1)
    aug = np.sqrt(n2.max() - n2)[:, None]
    rowsp = np.concatenate([rows, aug], 1)
    h = rng.standard_normal(d).astype(np.float32)
    qp = np.concatenate([h, [0.0]])
    ip_order = np.argsort(-(h @ W))
    l2_order = np.argsort(((rowsp - qp) ** 2).sum(1))
    np.testing.assert_array_equal(ip_order[:20], l2_order[:20])


def test_knn_softmax_head_end_to_end():
    from repro.serving.knn_softmax import KnnSoftmaxHead
    rng = np.random.default_rng(0)
    W = rng.standard_normal((32, 2048)).astype(np.float32)
    head = KnnSoftmaxHead(W, w=8, th=128, r_candidates=256, nbr_nodes=8)
    for _ in range(20):
        t = rng.integers(2048)
        h = W[:, t] + 0.1 * rng.standard_normal(32).astype(np.float32)
        head.step(h)
    s = head.stats
    assert s.tokens == 20
    assert s.exact_in_topr / s.tokens >= 0.5       # retrieval works


def test_knn_softmax_frontend_matches_step_batch(monkeypatch):
    """Decode rows routed one request each through the coalescing front-end
    (``make_frontend`` + ``step_batch_via``) run the same Alg. 4 visit set
    as the batched device path (``step_batch``): the same candidate ids per
    row, hence the same argmax tokens."""
    from repro.serving.knn_softmax import KnnSoftmaxHead
    rng = np.random.default_rng(3)
    W = rng.standard_normal((32, 2048)).astype(np.float32)
    head = KnnSoftmaxHead(W, w=8, th=128, r_candidates=64, nbr_nodes=4)
    tgt = rng.integers(2048, size=6)
    H = W[:, tgt].T + 0.1 * rng.standard_normal((6, 32)).astype(np.float32)
    seen = []
    select = head._select_tokens
    monkeypatch.setattr(head, "_select_tokens", lambda h, cand, track: (
        seen.append(np.asarray(cand)), select(h, cand, track))[1])
    toks = head.step_batch(H)
    frontend = head.make_frontend(max_batch=8, max_wait=1e-4)
    try:
        toks_via = head.step_batch_via(frontend, H)
    finally:
        frontend.close()
    cand, cand_via = seen
    assert cand.shape == cand_via.shape == (6, 64)
    for row, row_via in zip(cand, cand_via):
        assert set(row[row >= 0].tolist()) == set(row_via[row_via >= 0].tolist())
    np.testing.assert_array_equal(toks_via, toks)
    assert frontend.stats.completed == 6 and frontend.stats.failed == 0
