"""Fault-injection, crash-safety, and degraded-mode tests.

Three layers (docs/robustness.md):

1. The failpoint registry and WAL in isolation — action parsing, scoped
   arming, seeded determinism, retry/heal semantics, torn-tail repair.
2. Crash-at-every-failpoint persistence: a save interrupted at *any* site
   must leave the store loadable, and the loaded index must reproduce the
   full pre-crash in-memory state (old generation + WAL replay ≡ new
   generation), including fuzzy duplicates and tombstones.
3. Degraded-mode sharded search: dead shards drop out of the merge, the
   reported coverage is the reachable-live fraction, and surviving results
   are bitwise equal to a host search restricted to the surviving shards.
"""
import json
import os

import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core.build import DumpyParams
from repro.core.index import (DumpyIndex, IndexCorruptionError,
                              _params_to_json, _tree_to_json)
from repro.core.sax import SaxParams
from repro.core.search_device import (exact_search_device_batch,
                                      extended_search_device_batch,
                                      shard_coverage)
from repro.core.split import SplitParams
from repro.data.series import random_walks
from repro.robustness import failpoints as fp
from repro.robustness.wal import WriteAheadLog

FUZZY = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=64),
                    fuzzy_f=0.15)
FINE = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=64))


@pytest.fixture(autouse=True)
def _clean_registry():
    fp.REGISTRY.disarm()
    yield
    fp.REGISTRY.disarm()


# -- failpoint registry --------------------------------------------------------

def test_parse_action_specs():
    act = fp.parse_action("flaky:2")
    assert act.kind == "flaky" and act.times == 2
    assert fp.parse_action("flaky").times == 1
    assert fp.parse_action("delay:0.05").delay == 0.05
    act = fp.parse_action("raise:p=0.5:seed=7")
    assert act.p == 0.5 and act.seed == 7
    assert fp.parse_action("exit:3").code == 3
    assert fp.parse_action(fp.Action("crash")).kind == "crash"
    with pytest.raises(ValueError, match="unknown failpoint action"):
        fp.parse_action("explode")
    with pytest.raises(ValueError, match="unknown failpoint option"):
        fp.parse_action("raise:q=1")


def test_armed_scoping_and_nesting():
    fp.failpoint("a")                       # disarmed: no-op
    with fp.armed({"a": "raise"}):
        with pytest.raises(fp.FailpointError):
            fp.failpoint("a")
        with fp.armed(b="raise"):           # keyword form, __ → .
            assert fp.is_armed("b")
            with pytest.raises(fp.FailpointError):
                fp.failpoint("b")
        assert not fp.is_armed("b")
        assert fp.is_armed("a")             # inner exit left outer armed
    assert not fp.is_armed("a")
    fp.failpoint("a")


def test_flaky_heals_and_counts():
    with fp.armed({"s": "flaky:2"}):
        for _ in range(2):
            with pytest.raises(fp.FailpointError):
                fp.failpoint("s")
        fp.failpoint("s")                   # healed
        fp.failpoint("s")
        assert fp.REGISTRY.fires("s") == 2
        assert fp.REGISTRY.hits("s") == 4


def test_probabilistic_firing_is_seeded():
    def pattern():
        out = []
        with fp.armed({"s": "raise:p=0.4:seed=11"}):
            for _ in range(24):
                try:
                    fp.failpoint("s")
                    out.append(0)
                except fp.FailpointError:
                    out.append(1)
        return out

    first = pattern()
    assert 0 < sum(first) < 24              # actually probabilistic
    assert pattern() == first               # and exactly reproducible


def test_with_retries_recovers_and_exhausts():
    calls = []
    with fp.armed({"s": "flaky:2"}):
        def op():
            calls.append(1)
            fp.failpoint("s")
            return "ok"
        assert fp.with_retries(op, backoff=0.0001, site="s") == "ok"
    assert len(calls) == 3                  # 2 failures + 1 success

    with fp.armed({"s": "flaky:5"}):
        with pytest.raises(fp.RetriesExhausted) as ei:
            fp.with_retries(lambda: fp.failpoint("s"), retries=2,
                            backoff=0.0001, site="s")
    assert isinstance(ei.value.__cause__, fp.FailpointError)


def test_injected_crash_is_not_an_exception():
    assert not issubclass(fp.InjectedCrash, Exception)
    with fp.armed({"s": "crash"}):
        with pytest.raises(fp.InjectedCrash):
            # with_retries must not absorb a crash as a transient fault
            fp.with_retries(lambda: fp.failpoint("s"), site="s")


def test_arm_from_env_spec():
    reg = fp.FailpointRegistry()
    assert reg.arm_from_env("a=crash; b=flaky:2,c") == 3
    assert reg.is_armed("a") and reg.is_armed("b")
    assert reg._sites["c"].action.kind == "raise"   # bare site → raise
    assert reg._sites["b"].action.times == 2


# -- write-ahead log -----------------------------------------------------------

@settings(max_examples=8)
@given(st.integers(1, 5), st.integers(1, 48))
def test_wal_roundtrip_property(tmp_path_factory, n_batches, rows):
    # a fresh directory per example: hypothesis runs every example inside
    # one call of the test, so a function-scoped tmp_path would be shared
    wal = WriteAheadLog(str(tmp_path_factory.mktemp("wal") / "w.log"))
    rng = np.random.default_rng(n_batches * 100 + rows)
    batches = [rng.normal(size=(rows, 16)).astype(np.float32)
               for _ in range(n_batches)]
    for b in batches:
        wal.append(b)
    got = wal.replay()
    assert len(got) == n_batches
    for want, have in zip(batches, got):
        np.testing.assert_array_equal(want, have)


def test_wal_torn_tail_repaired(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "w.log"))
    b = np.ones((3, 8), np.float32)
    wal.append(b)
    wal.append(2 * b)
    with open(wal.path, "ab") as fh:
        fh.write(b"DWAL\x00garbage-torn-tail")
    torn_size = os.path.getsize(wal.path)
    got = wal.replay()
    assert len(got) == 2
    assert os.path.getsize(wal.path) < torn_size    # repaired
    wal.append(3 * b)                               # clean tail: appendable
    assert len(wal.replay()) == 3


def test_wal_digest_corruption_drops_record(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "w.log"))
    wal.append(np.ones((2, 8), np.float32))
    first_end = os.path.getsize(wal.path)
    wal.append(np.full((2, 8), 2, np.float32))
    with open(wal.path, "r+b") as fh:               # flip a payload byte of
        fh.seek(first_end + 60)                     # the second record
        byte = fh.read(1)
        fh.seek(first_end + 60)
        fh.write(bytes([byte[0] ^ 0xFF]))
    got = wal.replay()
    assert len(got) == 1
    np.testing.assert_array_equal(got[0], np.ones((2, 8), np.float32))


def test_wal_append_retries_transient_faults(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "w.log"))
    with fp.armed({"wal.append": "flaky:2"}):
        wal.append(np.ones((2, 8), np.float32))
        assert fp.REGISTRY.fires("wal.append") == 2
    assert len(wal.replay()) == 1


def test_wal_tear_crash_leaves_recoverable_log(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "w.log"))
    wal.append(np.ones((2, 8), np.float32))
    with fp.armed({"wal.append.tear": "crash"}):
        with pytest.raises(fp.InjectedCrash):
            wal.append(np.full((2, 8), 2, np.float32))
    got = wal.replay()                              # torn tail dropped
    assert len(got) == 1
    wal.append(np.full((2, 8), 3, np.float32))
    assert len(wal.replay()) == 2


# -- crash-safe persistence ----------------------------------------------------

def _build_fuzzy_with_tombstones():
    db = random_walks(1500, 64, seed=5)
    idx = DumpyIndex.build(db, FUZZY)
    assert idx.stats.n_duplicates > 0               # fuzzy replicas present
    for sid in (3, 111, 270, 1499):
        idx.delete(sid)
    return idx


SAVE_SITES = ("index.save.begin", "index.save.arrays", "index.save.meta",
              "index.save.manifest", "index.save.rename",
              "index.save.commit", "index.save.post_commit",
              "index.save.prune")


@pytest.mark.parametrize("site", SAVE_SITES)
def test_crash_at_every_save_failpoint(tmp_path, site):
    """A save crashed at any site must leave the store loadable, and the
    load must reproduce the complete pre-crash state — either the previous
    generation plus its WAL, or the freshly committed generation."""
    idx = _build_fuzzy_with_tombstones()
    path = str(tmp_path / "idx")
    idx.save(path)
    idx.insert_many(random_walks(9, 64, seed=6))    # → WAL of gen-000001
    with fp.armed({site: "crash"}):
        with pytest.raises(fp.InjectedCrash):
            idx.save(path)
    re = DumpyIndex.load(path)
    np.testing.assert_array_equal(re.db, idx.db)
    np.testing.assert_array_equal(re.alive, idx.alive)
    # post-crash saves are idempotent: stale tmp droppings are cleared
    idx.save(path)
    re2 = DumpyIndex.load(path)
    np.testing.assert_array_equal(re2.db, idx.db)
    np.testing.assert_array_equal(re2.alive, idx.alive)


def test_crash_in_wal_append_keeps_index_consistent(tmp_path):
    db = random_walks(400, 64, seed=7)
    idx = DumpyIndex.build(db, FINE)
    path = str(tmp_path / "idx")
    idx.save(path)
    batch = random_walks(5, 64, seed=8)
    for site in ("wal.append", "wal.append.tear"):
        with fp.armed({site: "crash"}):
            with pytest.raises(fp.InjectedCrash):
                idx.insert_many(batch)
        assert idx.db.shape[0] == 400        # durability-first: no mutation
        re = DumpyIndex.load(path)           # torn tail (if any) dropped
        np.testing.assert_array_equal(re.db, db)
    idx.insert_many(batch)                   # log is still appendable
    re = DumpyIndex.load(path)
    np.testing.assert_array_equal(re.db, idx.db)


def _flip_byte(path: str, off: int = 100) -> None:
    with open(path, "r+b") as fh:
        fh.seek(off)
        byte = fh.read(1)
        fh.seek(off)
        fh.write(bytes([byte[0] ^ 0xFF]))


def test_corrupt_generation_falls_back(tmp_path):
    idx = DumpyIndex.build(random_walks(400, 64, seed=9), FINE)
    path = str(tmp_path / "idx")
    idx.save(path)                                  # gen-000001
    idx.insert_many(random_walks(6, 64, seed=10))   # → wal-000001
    idx.save(path)                                  # gen-000002
    _flip_byte(os.path.join(path, "gen-000002", "arrays.npz"))
    re = DumpyIndex.load(path)                      # gen-000001 + its WAL
    np.testing.assert_array_equal(re.db, idx.db)


def test_all_generations_corrupt_raises(tmp_path):
    idx = DumpyIndex.build(random_walks(300, 64, seed=11), FINE)
    path = str(tmp_path / "idx")
    idx.save(path)
    idx.save(path)
    for gen in ("gen-000001", "gen-000002"):
        _flip_byte(os.path.join(path, gen, "arrays.npz"))
    with pytest.raises(IndexCorruptionError, match="no intact generation"):
        DumpyIndex.load(path)


def test_manifest_shape_mismatch_is_precise(tmp_path):
    idx = DumpyIndex.build(random_walks(300, 64, seed=12), FINE)
    path = str(tmp_path / "idx")
    idx.save(path)
    mpath = os.path.join(path, "gen-000001", "manifest.json")
    with open(mpath) as fh:
        manifest = json.load(fh)
    manifest["arrays"]["db"]["shape"] = [300, 63]
    with open(mpath, "w") as fh:
        json.dump(manifest, fh)
    with pytest.raises(IndexCorruptionError, match="manifest says"):
        DumpyIndex.load(path)


def test_unknown_format_version_rejected(tmp_path):
    idx = DumpyIndex.build(random_walks(300, 64, seed=13), FINE)
    path = str(tmp_path / "idx")
    idx.save(path)
    mpath = os.path.join(path, "gen-000001", "manifest.json")
    with open(mpath) as fh:
        manifest = json.load(fh)
    manifest["format_version"] = 99
    with open(mpath, "w") as fh:
        json.dump(manifest, fh)
    with pytest.raises(IndexCorruptionError, match="format_version"):
        DumpyIndex.load(path)


def test_legacy_flat_layout_loads(tmp_path):
    """Pre-generation stores (arrays.npz + meta.json directly under the
    path, no manifest) must keep loading."""
    idx = DumpyIndex.build(random_walks(300, 64, seed=14), FINE)
    path = str(tmp_path / "idx")
    os.makedirs(path)
    np.savez(os.path.join(path, "arrays.npz"),
             db=idx.db, paa=idx.paa, sax=idx.sax, alive=idx.alive,
             leaf_sym=idx.flat.leaf_sym, leaf_card=idx.flat.leaf_card,
             leaf_offsets=idx.flat.leaf_offsets, order=idx.flat.order)
    import dataclasses as _dc
    meta = {"params": _params_to_json(idx.params),
            "stats": _dc.asdict(idx.stats),
            "tree": _tree_to_json(idx.root)}
    with open(os.path.join(path, "meta.json"), "w") as fh:
        json.dump(meta, fh)
    re = DumpyIndex.load(path)
    np.testing.assert_array_equal(re.db, idx.db)
    assert re._wal.path.endswith("wal-legacy.log")


def test_load_restores_clean_state_and_wal(tmp_path):
    idx = DumpyIndex.build(random_walks(300, 64, seed=15), FINE)
    path = str(tmp_path / "idx")
    idx.save(path)
    re = DumpyIndex.load(path)
    assert re._dirty is False
    assert not re._device_cache
    assert re._wal is not None and re._store_path == path
    re.insert_many(random_walks(3, 64, seed=16))    # WAL-logged
    assert re._dirty is True
    again = DumpyIndex.load(path)                   # replays that WAL
    np.testing.assert_array_equal(again.db, re.db)
    assert again._dirty is True                     # replay = pending inserts


# -- query-boundary guards -----------------------------------------------------

@pytest.fixture(scope="module")
def guarded():
    db = random_walks(500, 64, seed=20)
    return DumpyIndex.build(db, FINE)


@pytest.mark.parametrize("metric", ["ed", "dtw"])
def test_query_guards_exact_batch(guarded, metric):
    q = random_walks(2, 64, seed=21)
    bad = q.copy()
    bad[1, 3] = np.nan
    with pytest.raises(ValueError, match="NaN/Inf"):
        exact_search_device_batch(guarded, bad, 5, metric=metric)
    bad[1, 3] = np.inf
    with pytest.raises(ValueError, match="NaN/Inf"):
        exact_search_device_batch(guarded, bad, 5, metric=metric)
    with pytest.raises(ValueError, match="query length"):
        exact_search_device_batch(guarded, q[:, :32], 5, metric=metric)
    with pytest.raises(ValueError, match=r"\[Q, n\]"):
        exact_search_device_batch(guarded, q[None], 5, metric=metric)
    with pytest.raises(TypeError, match="real-numeric"):
        exact_search_device_batch(guarded, q.astype(np.complex64), 5,
                                  metric=metric)
    # integer queries are fine (cast at the boundary)
    ids, _, _ = exact_search_device_batch(
        guarded, np.zeros((1, 64), np.int32), 5, metric=metric)
    assert (ids[0] >= 0).all()


@pytest.fixture(scope="module")
def head():
    from repro.serving.knn_softmax import KnnSoftmaxHead
    rng = np.random.default_rng(22)
    lm_head = rng.normal(size=(15, 400)).astype(np.float32)
    return KnnSoftmaxHead(lm_head, w=8, th=64, r_candidates=16, nbr_nodes=4)


def test_hidden_state_guards(head):
    h = np.zeros(15, np.float32)
    h_bad = h.copy()
    h_bad[0] = np.nan
    with pytest.raises(ValueError, match="NaN/Inf"):
        head.candidates(h_bad)
    with pytest.raises(ValueError, match="NaN/Inf"):
        head.candidates_batch(np.stack([h, h_bad]))
    with pytest.raises(ValueError, match="d_model"):
        head.candidates(np.zeros(14, np.float32))
    with pytest.raises(TypeError, match="real-numeric"):
        head.candidates_batch(h[None].astype(np.complex64))
    assert len(head.candidates(h)) > 0


def test_head_shard_health_api(head):
    with pytest.raises(ValueError, match="entries"):
        head.set_shard_health((True, True))         # 1-shard device index
    with pytest.raises(ValueError, match="every shard dead"):
        head.set_shard_health((False,))
    head.set_shard_health((True,))
    head.candidates_batch(np.zeros((2, 15), np.float32))
    assert head.last_coverage == 1.0
    head.set_shard_health(None)
    assert head._shard_health is None


# -- degraded-mode sharded search ----------------------------------------------

@pytest.fixture(scope="module")
def sharded():
    db = random_walks(4000, 64, seed=30)
    idx = DumpyIndex.build(db, FINE)
    dev = idx.device_index(n_shards=4)
    sizes = np.diff(dev.row_bounds)
    assert (sizes > 0).all()                        # all 4 shards hold data
    return db, idx, dev


def _surviving_mask(idx, dev, health):
    order = np.asarray(idx.flat.order)
    rb = dev.row_bounds
    mask = np.zeros(idx.db.shape[0], bool)
    for s, h in enumerate(health):
        if h:
            mask[order[rb[s]:rb[s + 1]]] = True
    return mask


def test_degraded_coverage_and_bitwise_parity(sharded):
    db, idx, dev = sharded
    qs = random_walks(6, 64, seed=31)
    health = (True, True, True, False)
    ids, d, _, cov = exact_search_device_batch(idx, qs, 10, dev=dev,
                                               shard_health=health)
    surviving = _surviving_mask(idx, dev, health)
    assert 0.0 < cov < 1.0
    assert cov == surviving.mean()
    assert cov == shard_coverage(idx, dev.with_shard_health(health))
    sub = np.where(surviving)[0]
    dist = np.sqrt(((db[sub][None] - qs[:, None]) ** 2).sum(-1)) \
        .astype(np.float32)
    for q in range(len(qs)):
        perm = np.lexsort((sub, dist[q]))[:10]
        np.testing.assert_array_equal(sub[perm], ids[q])
        np.testing.assert_array_equal(dist[q][perm].astype(np.float32), d[q])


def test_all_healthy_mask_is_identity(sharded):
    _, idx, dev = sharded
    qs = random_walks(4, 64, seed=32)
    ids0, d0, _ = exact_search_device_batch(idx, qs, 10, dev=dev)
    ids1, d1, _, cov = exact_search_device_batch(
        idx, qs, 10, dev=dev, shard_health=(True,) * 4)
    assert cov == 1.0
    assert dev.with_shard_health((True,) * 4).shard_health is None
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_array_equal(d0, d1)


def test_degraded_dtw_returns_only_surviving(sharded):
    _, idx, dev = sharded
    qs = random_walks(3, 64, seed=33)
    health = (False, True, True, True)
    ids, d, _, cov = exact_search_device_batch(
        idx, qs, 8, dev=dev, metric="dtw", shard_health=health)
    surviving = _surviving_mask(idx, dev, health)
    assert cov == surviving.mean()
    got = ids[ids >= 0]
    assert surviving[got].all()                     # no dead-shard leakage
    assert (np.diff(d, axis=1)[np.isfinite(d)[:, 1:]] >= 0).all()


def test_degraded_extended_search(sharded):
    _, idx, dev = sharded
    qs = random_walks(3, 64, seed=34)
    health = (True, False, True, True)
    res = extended_search_device_batch(idx, qs, 8, nbr=4, dev=dev,
                                       shard_health=health)
    assert len(res) == 4
    ids, cov = res[0], res[3]
    surviving = _surviving_mask(idx, dev, health)
    assert cov == surviving.mean()
    got = ids[ids >= 0]
    assert surviving[got].all()


def test_with_shard_health_validation(sharded):
    _, _, dev = sharded
    with pytest.raises(ValueError, match="entries"):
        dev.with_shard_health((True, False))
    with pytest.raises(ValueError, match="every shard dead"):
        dev.with_shard_health((False,) * 4)
    assert dev.with_shard_health(None).shard_health is None
    masked = dev.with_shard_health([1, 0, 1, 1])
    assert masked.shard_health == (True, False, True, True)
    assert masked.n_live_shards == 3


def test_shard_merge_failpoint_retry_and_crash(sharded):
    _, idx, dev = sharded
    qs = random_walks(2, 64, seed=35)
    with fp.armed({"search.shard_merge": "flaky:1"}):
        ids, _, _ = exact_search_device_batch(idx, qs, 5, dev=dev)
        assert fp.REGISTRY.fires("search.shard_merge") == 1
    assert (ids >= 0).all()
    with fp.armed({"search.shard_merge": "crash"}):
        with pytest.raises(fp.InjectedCrash):
            exact_search_device_batch(idx, qs, 5, dev=dev)


def test_device_put_failpoint_retry():
    idx = DumpyIndex.build(random_walks(300, 64, seed=36), FINE)
    with fp.armed({"device.put": "flaky:2"}):
        dev = idx.device_index()
        assert fp.REGISTRY.fires("device.put") == 2
    assert int(dev.row_bounds[-1]) >= 300   # the upload still completed
