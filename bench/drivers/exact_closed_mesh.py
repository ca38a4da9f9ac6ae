"""Closed-loop exact kNN on a mesh: the loop of ``exact_closed`` over a
collection sharded leaf-aligned across the configuration's ``mesh``, one
shard a chip, through ``exact_search_device_batch(..., mesh=...)``.

Set-up makes the collection shard by shard, each shard on its own chip
(``bench.data_shards``: bitwise the rows of ``bench.data.walks``), copies
it to the host once, builds the index with the device backend on the mesh
(each shard's rows laid out on the chip that owns it) and places the
``DeviceIndex``.  The check regenerates each shard on its chip and runs the
plain reference there (``bench.reference_sharded``).

Traffic parameters as ``exact_closed``: ``batch``, ``k``, ``pool`` and the
``sample`` of answered queries the check compares.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, data_shards, deploy, reference_sharded
from bench.harness import Outcome


def run(ctx) -> Outcome:
    from repro.core import search_device as sd
    from repro.core.index import DumpyIndex
    from repro.distributed.sharding import make_mesh
    tr, cfg = ctx.traffic, ctx.config
    B, k, P = tr["batch"], tr["k"], tr["pool"]
    N, n = cfg["collection"]["n_series"], cfg["collection"]["length"]
    mesh = make_mesh(tuple(cfg["mesh"].values()), tuple(cfg["mesh"]))
    devices = list(mesh.devices.flat)
    S = len(devices)
    make_shard = functools.partial(data_shards.shard, ctx.seed,
                                   deploy.COLLECTION, N, n, devices=devices)

    with ctx.timed("data_s"):
        db = data_shards.host_collection(ctx.seed, deploy.COLLECTION, N, n,
                                         devices)
    with ctx.timed("build_s"):
        index = DumpyIndex.build(db, deploy.params(cfg), backend="device",
                                 mesh=mesh)
        dev = index.device_index(n_shards=S, mesh=mesh)
        jax.block_until_ready(dev)
    pool = deploy.queries(cfg, ctx.seed, P)
    n_batches = P // B
    with ctx.timed("warmup_s"):
        for _ in range(2):
            sd.exact_search_device_batch(index, pool[:B], k, mesh=mesh,
                                         dev=dev)

    calls = []                                   # (batch no, ids, d, visited)
    with ctx.window() as t0:
        while time.perf_counter() - t0 < ctx.seconds:
            b = len(calls) % n_batches
            with jax.profiler.TraceAnnotation("bench.exact_call"):
                ids, d, vis = sd.exact_search_device_batch(
                    index, pool[b * B:(b + 1) * B], k, mesh=mesh, dev=dev)
            calls.append((b, ids, d, vis))
        t_end = time.perf_counter()
    elapsed = t_end - t0
    span_rows = np.asarray(dev.win_size).reshape(-1)
    del index, dev, db

    rng = np.random.default_rng([ctx.seed % (1 << 64), 3])
    picks = rng.choice(len(calls) * B, size=min(tr["sample"], len(calls) * B),
                       replace=False)
    sample = [(calls[p // B], p % B) for p in np.sort(picks)]

    def check_fn(control: bool) -> dict:
        qs = np.stack([pool[c[0] * B + lane] for c, lane in sample])
        ref_i, ref_d = reference_sharded.knn(make_shard, S, qs, k)
        if control:
            srv_i, srv_d = reference_sharded.knn(make_shard, S, qs, k,
                                                 dtype=jnp.bfloat16)
            srv_i, srv_d = list(srv_i), list(srv_d)
        else:
            srv_i = [c[1][lane] for c, lane in sample]
            srv_d = [c[2][lane] for c, lane in sample]
        true_d = reference_sharded.distances(make_shard, S, qs,
                                             check.pad_ids(srv_i, k))
        return {"knn_gap": check.knn_gap(srv_i, srv_d, ref_d, true_d)}

    return Outcome(
        metrics={"exact_qps": len(calls) * B / elapsed},
        attempted=len(calls) * B, failed=0,
        counters={"exact_calls": len(calls),
                  "spans_visited": [c[3] for c in calls],
                  "span_rows": span_rows, "n": n},
        check=check_fn)
