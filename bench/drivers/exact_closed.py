"""Closed-loop exact kNN: batches of distinct held-out queries, back to back,
through ``exact_search_device_batch`` (span loop on the device, host
re-rank included), for the whole window.

Traffic parameters: ``batch`` queries a call, ``k``, a ``pool`` of held-out
queries the batches walk through in order (a multiple of ``batch``), and
the ``sample`` of answered queries the check compares.

``exact_qps`` is every query answered over the time from the window's
start to the end of its last call (the last call starts inside the
window).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, deploy, reference
from bench.harness import Outcome


def run(ctx) -> Outcome:
    from repro.core import search_device as sd
    tr = ctx.traffic
    B, k, P = tr["batch"], tr["k"], tr["pool"]
    index, dev = deploy.build(ctx)
    pool = deploy.queries(ctx.config, ctx.seed, P)
    n_batches = P // B
    with ctx.timed("warmup_s"):
        for _ in range(2):
            sd.exact_search_device_batch(index, pool[:B], k, dev=dev)

    calls = []                                   # (batch no, ids, d, visited)
    with ctx.window() as t0:
        while time.perf_counter() - t0 < ctx.seconds:
            b = len(calls) % n_batches
            with jax.profiler.TraceAnnotation("bench.exact_call"):
                ids, d, vis = sd.exact_search_device_batch(
                    index, pool[b * B:(b + 1) * B], k, dev=dev)
            calls.append((b, ids, d, vis))
        t_end = time.perf_counter()
    elapsed = t_end - t0
    span_rows = np.asarray(dev.win_size).reshape(-1)
    n = int(dev.n)
    del index, dev

    rng = np.random.default_rng([ctx.seed % (1 << 64), 3])
    picks = rng.choice(len(calls) * B, size=min(tr["sample"], len(calls) * B),
                       replace=False)
    sample = [(calls[p // B], p % B) for p in np.sort(picks)]

    def check_fn(control: bool) -> dict:
        db = deploy.collection_device(ctx.config, ctx.seed)
        qs = np.stack([pool[c[0] * B + lane] for c, lane in sample])
        ref_i, ref_d = reference.knn(db, qs, k)
        if control:
            srv_i, srv_d = reference.knn(db, qs, k, dtype=jnp.bfloat16)
            srv_i, srv_d = list(srv_i), list(srv_d)
        else:
            srv_i = [c[1][lane] for c, lane in sample]
            srv_d = [c[2][lane] for c, lane in sample]
        true_d = reference.distances(db, qs, check.pad_ids(srv_i, k))
        return {"knn_gap": check.knn_gap(srv_i, srv_d, ref_d, true_d)}

    return Outcome(
        metrics={"exact_qps": len(calls) * B / elapsed},
        attempted=len(calls) * B, failed=0,
        counters={"exact_calls": len(calls),
                  "spans_visited": [c[3] for c in calls],
                  "span_rows": span_rows, "n": n},
        check=check_fn)
