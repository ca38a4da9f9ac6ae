"""Drive the Dumpy index once through its user entry points on a TPU, at the
paper's Rand collection (2^22 z-normalised random walks of length 256, the
``DumpyParams()`` defaults w=16, b=8, th=10,000), and check every answer
against the host reference on the same data.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py [--seed N] --four-chips # sharded search, 4 chips

One chip: device build, exact ED search (batch 64, k=10) against
``baselines/brute.py``, extended search (nbr=4) through the
``CoalescingFrontend`` against host ``extended_search``, and exact DTW on a
32,768-series sub-collection (both candidate orders) against host
``exact_search(metric="dtw")``.  Four chips: ``search_distributed`` on a
4-way ``data`` mesh, exact and nbr=4, healthy against the 1-shard device
result and with one shard dead against the host search restricted to the
surviving shards.

Every line but the last is one JSON record of a phase, naming the device
it ran on.  The last line is ``{"ok": true, "device": {...}}``.  Off a TPU,
or on any mismatch, the script exits non-zero and prints no result line.
It runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402

N_SERIES = 1 << 22          # 4 GiB of f32: a quarter of a v5e's HBM
LENGTH = 256
K = 10
BATCH = 64                  # exact-search batch
NBR = 4                     # extended-search leaf budget
N_REQUESTS = 48             # requests sent through the front-end
MAX_BATCH = 8               # front-end ladder 1, 2, 4, 8
DTW_SERIES = 1 << 15        # DTW sub-collection: the host DP must finish
DTW_BATCH = 16
DEAD_SHARD = (True, True, True, False)
THREADS = max(1, min(16, os.cpu_count() or 1))


class SmokeFailure(RuntimeError):
    """A phase's answer differs from its reference."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Log:
    """One JSON record per line, each naming the device it ran on."""

    def __init__(self, devices):
        self.devices = devices
        self.kind = devices[0].device_kind

    def peak(self, i: int = 0) -> int | None:
        stats = self.devices[i].memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def __call__(self, phase: str, **rec) -> None:
        rec = {"phase": phase, **rec, "device": self.kind,
               "peak_bytes_in_use": self.peak()}
        print(json.dumps(rec), flush=True)


def _same_knn(ids, d, ref_ids, ref_d, what: str) -> None:
    """Exact answers: ids equal in order, distances equal to f32 rounding."""
    ref_ids = np.asarray(ref_ids)
    require(np.array_equal(ids[:len(ref_ids)], ref_ids),
            f"{what}: ids {ids.tolist()} != reference {ref_ids.tolist()}")
    require(np.allclose(d[:len(ref_d)], ref_d, rtol=1e-5, atol=0.0),
            f"{what}: distances differ from the reference")


def _same_lane(ids, d, ref_ids, ref_d, what: str) -> None:
    """Device-ranked answers (no host re-rank): the same ids as the
    reference at every rank up to f32 near-ties, which may swap order."""
    ref_ids = np.asarray(ref_ids)
    require(len(ids) == len(ref_ids)
            and set(ids.tolist()) == set(ref_ids.tolist()),
            f"{what}: ids {ids.tolist()} != reference {ref_ids.tolist()}")
    require(np.allclose(d, ref_d, rtol=1e-5, atol=1e-6),
            f"{what}: distances differ from the reference")


def _brute(db: np.ndarray, qs: np.ndarray, k: int) -> list:
    from repro.core.baselines.brute import brute_force_knn
    with ThreadPoolExecutor(THREADS) as ex:
        return list(ex.map(lambda q: brute_force_knn(db, q, k), qs))


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def make_data(seed: int, n_series: int, log: Log):
    from repro.data.series import query_workload, random_walks
    t0 = time.perf_counter()
    db = random_walks(n_series, LENGTH, seed=seed)
    qs = query_workload(BATCH, LENGTH, seed=seed + 10_007)
    log("data", dataset="Rand", series=n_series, length=LENGTH,
        queries=BATCH, seed=seed,
        generate_s=time.perf_counter() - t0)
    return db, qs


def build(db: np.ndarray, log: Log, phase: str = "build"):
    import jax
    from repro.core.build import DumpyParams
    from repro.core.index import DumpyIndex
    t0 = time.perf_counter()
    index = DumpyIndex.build(db, DumpyParams(), backend="device")
    t1 = time.perf_counter()
    dev = index.device_index()
    jax.block_until_ready(dev)
    t2 = time.perf_counter()
    log(phase, series=int(db.shape[0]), leaves=int(index.flat.n_leaves),
        max_leaf=int(dev.lmax), build_s=t1 - t0, device_index_s=t2 - t1)
    return index, dev


def exact_ed(index, dev, db, qs, log: Log) -> None:
    import jax.numpy as jnp
    from repro.core import search_device as sd
    from repro.core.metric import resolve
    from repro.kernels.sax_encode import sax_encode
    sax = index.params.sax
    met = resolve("ed", LENGTH)
    qs_dev = jnp.asarray(qs)
    # the compiled programs must hold the Pallas kernels, not the jnp twins
    t0 = time.perf_counter()
    enc = sax_encode.lower(qs_dev, w=sax.w, b=sax.b).compile()
    prep, _ = sd._prep_batch(met, qs_dev, sax.w, sax.b)
    kk = sd._result_margin(dev, K) + 8
    prog = sd._exact_knn_sharded.lower(dev, prep, qs_dev, k=kk,
                                       metric=met).compile()
    compile_s = time.perf_counter() - t0
    require(_custom_calls(enc) >= 1, "sax_encode compiled without its kernel")
    require(_custom_calls(prog) >= 1,
            "exact-search program compiled without tpu_custom_call")

    t0 = time.perf_counter()
    ids, d, visited = sd.exact_search_device_batch(index, qs, K, dev=dev)
    first_s = time.perf_counter() - t0
    lat = []
    for _ in range(3):
        t0 = time.perf_counter()
        ids2, d2, _ = sd.exact_search_device_batch(index, qs, K, dev=dev)
        lat.append(time.perf_counter() - t0)
    require(np.array_equal(ids, ids2) and np.array_equal(d, d2),
            "exact search is not deterministic across calls")

    t0 = time.perf_counter()
    ref = _brute(db, qs, K)
    ref_s = time.perf_counter() - t0
    for i, (rid, rd) in enumerate(ref):
        _same_knn(ids[i], d[i], rid, rd, f"exact ED query {i}")
    log("exact_ed", batch=BATCH, k=K, compile_s=compile_s,
        kernel_calls_in_program=_custom_calls(prog), first_call_s=first_s,
        latency_s=sorted(lat), spans_visited_mean=float(np.mean(visited)),
        reference="baselines.brute", reference_s=ref_s, matched=BATCH)


def extended_frontend(index, dev, qs, log: Log) -> None:
    from repro.core.search import extended_search
    from repro.serving.batching import CoalescingFrontend
    reqs = qs[:N_REQUESTS]
    t0 = time.perf_counter()
    with CoalescingFrontend(index, k_max=K, nbr_max=NBR, max_batch=MAX_BATCH,
                            dev=dev) as fe:
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        sent, futs = [], []
        for q in reqs:
            sent.append(time.perf_counter())
            futs.append(fe.submit(q, k=K, nbr=NBR))
        res = [f.result(timeout=600) for f in futs]
        serve_s = time.perf_counter() - t0
        stats = fe.stats.snapshot()
    lat = sorted(r.t_done - s for r, s in zip(res, sent))
    t0 = time.perf_counter()
    for i, (q, r) in enumerate(zip(reqs, res)):
        h_ids, h_d, _ = extended_search(index, q, K, NBR)
        _same_lane(r.ids, r.d, h_ids, h_d, f"front-end lane {i}")
    ref_s = time.perf_counter() - t0
    log("extended_frontend", requests=N_REQUESTS, k=K, nbr=NBR,
        buckets=list(fe.buckets), warmup_s=warm_s, serve_s=serve_s,
        latency_p50_s=lat[len(lat) // 2], latency_max_s=lat[-1],
        batches=stats["batches"], mean_occupancy=stats["mean_occupancy"],
        reference="core.search.extended_search", reference_s=ref_s,
        matched=N_REQUESTS)


def exact_dtw(db, seed: int, log: Log) -> None:
    from repro.core.search import exact_search
    from repro.core.search_device import exact_search_device_batch
    from repro.data.series import query_workload
    sub = db[:DTW_SERIES]
    qs = query_workload(DTW_BATCH, LENGTH, seed=seed + 30_011)
    index, dev = build(sub, log, phase="build_dtw_subcollection")
    t0 = time.perf_counter()
    ref = [exact_search(index, q, K, metric="dtw")[:2] for q in qs]
    ref_s = time.perf_counter() - t0
    for order in ("cluster", "shared"):
        t0 = time.perf_counter()
        ids, d, _ = exact_search_device_batch(index, qs, K, dev=dev,
                                              metric="dtw", order=order)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        exact_search_device_batch(index, qs, K, dev=dev, metric="dtw",
                                  order=order)
        lat = time.perf_counter() - t0
        for i, (rid, rd) in enumerate(ref):
            _same_knn(ids[i], d[i], rid, rd, f"exact DTW ({order}) query {i}")
        log("exact_dtw", order=order, series=DTW_SERIES, batch=DTW_BATCH,
            k=K, first_call_s=first_s, latency_s=lat,
            reference="core.search.exact_search(metric='dtw')",
            reference_s=ref_s, matched=DTW_BATCH)


def one_chip(seed: int, log: Log) -> None:
    db, qs = make_data(seed, N_SERIES, log)
    index, dev = build(db, log)
    exact_ed(index, dev, db, qs, log)
    extended_frontend(index, dev, qs, log)
    exact_dtw(db, seed, log)


def four_chips(seed: int, log: Log) -> None:
    from repro.core.distributed import search_distributed
    from repro.core.search import extended_search
    from repro.core.search_device import (exact_search_device_batch,
                                          extended_search_device_batch)
    from repro.distributed.sharding import logical_rules, make_mesh
    require(len(log.devices) == 4,
            f"--four-chips needs 4 devices, found {len(log.devices)}")
    db, qs = make_data(seed, N_SERIES, log)
    index, dev1 = build(db, log)
    e1 = exact_search_device_batch(index, qs, K, dev=dev1)
    x1 = extended_search_device_batch(index, qs, K, nbr=NBR, dev=dev1)

    mesh = make_mesh((4,), ("data",))
    with logical_rules(mesh):
        t0 = time.perf_counter()
        e4 = search_distributed(index, qs, K)
        x4 = search_distributed(index, qs, K, nbr=NBR)
        sharded_s = time.perf_counter() - t0
        dev4 = index.device_index(n_shards=4, mesh=mesh)
        t0 = time.perf_counter()
        ed = search_distributed(index, qs, K, shard_health=DEAD_SHARD)
        xd = search_distributed(index, qs, K, nbr=NBR,
                                shard_health=DEAD_SHARD)
        degraded_s = time.perf_counter() - t0

    # each shard on its own chip, none of them gathered on device 0
    owners = {s.device for s in dev4.db.addressable_shards}
    require(len(owners) == 4 and all(
        s.data.shape[0] == 1 for s in dev4.db.addressable_shards),
        "the [S, Tp, n] slab is not one shard per chip")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in log.devices]
    for name, a, b in (("exact", e1, e4), ("extended nbr=4", x1, x4)):
        require(np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1]),
                f"4-shard {name} differs from the 1-shard device result")
    log("sharded", shards=4, k=K, nbr=NBR, batch=BATCH, search_s=sharded_s,
        rows_per_shard=int(dev4.shard_rows), bytes_in_use_per_chip=in_use,
        compared_with="1-shard device result", matched=BATCH)

    # the host search restricted to the surviving shards' series
    alive = np.zeros(db.shape[0], bool)
    order = np.asarray(index.flat.order)
    rb = dev4.row_bounds
    for s, healthy in enumerate(DEAD_SHARD):
        if healthy:
            alive[order[rb[s]:rb[s + 1]]] = True
    require(ed[2] == xd[2] == alive.mean(),
            f"coverage {ed[2]} / {xd[2]} != surviving share {alive.mean()}")
    sub = np.flatnonzero(alive)
    t0 = time.perf_counter()
    ref = _brute(db[sub], qs, K)
    for i, (rid, rd) in enumerate(ref):
        _same_knn(ed[0][i], ed[1][i], sub[rid], rd, f"degraded exact query {i}")
    budget = NBR * int(dev4.lmax)   # every candidate of the nbr leaves
    for i, q in enumerate(qs):
        h_ids, h_d, _ = extended_search(index, q, budget, NBR)
        keep = alive[h_ids]
        _same_knn(xd[0][i], xd[1][i], h_ids[keep][:K], h_d[keep][:K],
                  f"degraded extended query {i}")
    log("degraded", shards=4, dead_shard=DEAD_SHARD.index(False),
        coverage=float(ed[2]), k=K, nbr=NBR, batch=BATCH,
        search_s=degraded_s, reference="restricted host search",
        reference_s=time.perf_counter() - t0, matched=BATCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the collection and the queries")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-search checks on 4 chips")
    args = ap.parse_args(argv)
    use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this check runs on the chip only", file=sys.stderr)
        return 2
    log = Log(devices)
    if args.four_chips:
        four_chips(args.seed, log)
    else:
        one_chip(args.seed, log)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
