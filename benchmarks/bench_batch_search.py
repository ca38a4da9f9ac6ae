"""Batched device-resident search throughput (ROADMAP: serving scale).

Measures queries/second of the batched exact path
(``exact_search_device_batch``) against looping the single-query
``exact_search_device``, plus the batched approximate path and the extended
(Alg. 4) path over an ``nbr`` sweep — recall@k against brute force next to
QPS, the serving recall/latency operating curve — at several batch sizes.
Steady-state numbers: each configuration is warmed once so XLA compilation
is excluded (the serving regime — programs are compiled at index load, not
per request).

The ``--metric dtw`` sweep (in ``both`` by default) runs the same paths at
``metric="dtw"`` on a DP-scaled collection: the batched exact DTW search,
the extended ``nbr`` sweep with recall@k, and the acceptance comparison of
the fused LB_Keogh-masked band-DP top-k (``dtw_topk_masked_jnp``) against
the full-DP scan (``dtw_topk_batch_jnp``) at the same batch.

Emits ``BENCH_batch_search.json`` next to the repo root (machine-readable)
and, when a previous run's file exists, prints the QPS delta against it —
with a loud warning on any >10% regression — so PRs track throughput drift.

    PYTHONPATH=src python -m benchmarks.bench_batch_search            # full
    PYTHONPATH=src python -m benchmarks.bench_batch_search --quick    # smoke

``--quick`` is a seconds-scale smoke (small collection, batch 8, including
a DTW smoke) wired into ``scripts/verify.sh``; it exercises the full paths
but does not overwrite the committed baseline JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np
import jax.numpy as jnp

from repro.core.baselines.brute import brute_force_knn
from repro.core.index import DumpyIndex
from repro.core.lb import dtw_topk_batch_jnp, dtw_topk_masked_jnp
from repro.core.metric import default_band
from repro.core.search_device import (approximate_search_device_batch,
                                      exact_search_device,
                                      exact_search_device_batch,
                                      extended_search_device_batch)
from repro.data.series import random_walks
from . import common

BATCHES = (8, 64)
NBR_SWEEP = (1, 4, 16)          # extended-search recall/QPS trade-off series
K = 10
REGRESSION_TOL = 0.10           # warn when QPS drops by more than this
DTW_N, DTW_LEN = 4000, 64       # DP-scaled DTW collection (CPU stand-in)
OUT_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_batch_search.json")


def _time(fn, repeat: int = 3) -> float:
    import jax

    jax.block_until_ready(fn())         # warmup: compile + caches
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn()
    jax.block_until_ready(out)          # async dispatch: sync before stopping
    return (time.perf_counter() - t0) / repeat


def _load_previous(out_json: str) -> dict | None:
    try:
        with open(out_json) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def _report_deltas(record: dict, prev: dict | None,
                   rows: list[tuple[str, float, str]]) -> int:
    """Append QPS-delta rows vs the previous run; returns #regressions."""
    if not prev or "batches" not in prev:
        rows.append(("batch_search/delta", 0.0, "no previous baseline"))
        return 0
    regressions = 0
    for B, cur in record["batches"].items():
        old = prev["batches"].get(B)
        if not old:
            continue
        keys = ["qps_exact_batch", "qps_approx_batch"]
        keys += [f"qps_extended_nbr{n}" for n in NBR_SWEEP]
        keys += ["qps_dtw_exact_batch", "qps_dtw_topk_full",
                 "qps_dtw_topk_masked"]
        keys += [f"qps_dtw_extended_nbr{n}" for n in NBR_SWEEP]
        # recall keys ride the same >10% warning machinery: exact recall
        # must stay 1.0 and the extended operating curve must not sag
        keys += ["recall_dtw_exact"]
        keys += [f"recall_dtw_extended_nbr{n}" for n in NBR_SWEEP]
        for key in keys:
            if key not in old or not old[key] or key not in cur:
                continue
            delta = cur[key] / old[key] - 1.0
            note = f"{delta:+.1%} vs previous"
            if delta < -REGRESSION_TOL:
                regressions += 1
                note += f"  ** WARNING: >{REGRESSION_TOL:.0%} QPS regression **"
                print(f"WARNING: {key}/B{B} regressed {delta:+.1%} "
                      f"({old[key]:.1f} -> {cur[key]:.1f} qps)",
                      file=sys.stderr)
            rows.append((f"batch_search/delta/{key}/B{B}",
                         100.0 * delta, note))
    return regressions


def _run_dtw(record: dict, rows: list, batches: tuple, sweep: tuple,
             quick: bool) -> None:
    """The ``metric="dtw"`` sweep: batched exact DTW + extended nbr series,
    plus the fused masked band-DP top-k vs the full-DP scan (the acceptance
    comparison) — on a DP-scaled collection (the band DP is O(n·band) per
    candidate; the ED collection would make the full-DP baseline take
    minutes on CPU)."""
    n_d = 1500 if quick else DTW_N
    len_d = DTW_LEN
    db = common.dataset("rand", n=n_d, length=len_d)
    idx = DumpyIndex.build(db, common.params())
    band = default_band(len_d)
    record["dtw"] = {"n_series": n_d, "length": len_d, "band": band,
                     "n_leaves": int(idx.flat.n_leaves)}
    xs_j = jnp.asarray(db)
    for B in batches:
        qs = random_walks(B, len_d, seed=9100 + B)
        qj = jnp.asarray(qs)
        # exact ground truth + the full-DP baseline timing
        gt_d, gt_ids = dtw_topk_batch_jnp(qj, xs_j, band, K)
        gt = [set(np.asarray(gt_ids)[i].tolist()) for i in range(B)]
        t_full = _time(
            lambda: np.asarray(dtw_topk_batch_jnp(qj, xs_j, band, K)[0]),
            repeat=1)
        t_masked = _time(
            lambda: np.asarray(dtw_topk_masked_jnp(qj, xs_j, band, K)[0]),
            repeat=1)
        t_exact = _time(
            lambda: exact_search_device_batch(idx, qs, K, metric="dtw"),
            repeat=1)
        ids_e, _, _, st = exact_search_device_batch(idx, qs, K, metric="dtw",
                                                    return_stats=True)
        recall_e = float(np.mean(
            [len(gt[i] & set(ids_e[i][ids_e[i] >= 0].tolist())) / K
             for i in range(B)]))
        rec_b = record["batches"].setdefault(str(B), {})
        rec_b["qps_dtw_topk_full"] = B / t_full
        rec_b["qps_dtw_topk_masked"] = B / t_masked
        rec_b["dtw_masked_speedup"] = t_full / t_masked
        rec_b["qps_dtw_exact_batch"] = B / t_exact
        rec_b["recall_dtw_exact"] = recall_e
        rec_b["dtw_cascade"] = st         # per-stage prune-rate counters
        rows.append((f"batch_search/dtw_topk_full/B{B}", B / t_full, "qps"))
        rows.append((f"batch_search/dtw_topk_masked/B{B}", B / t_masked,
                     f"qps;speedup={t_full / t_masked:.2f}x"))
        rows.append((f"batch_search/dtw_exact_batch/B{B}", B / t_exact,
                     f"qps;recall@{K}={recall_e:.3f}"))
        dead = st["killed_lb_keogh"] + st["killed_lb_improved"] \
            + st["dp_abandoned"]
        rows.append((f"batch_search/dtw_cascade/B{B}",
                     100.0 * dead / max(st["considered"], 1),
                     "% lanes killed before/inside DP "
                     f"(lbk={st['killed_lb_keogh']} "
                     f"lbi={st['killed_lb_improved']} "
                     f"dp_ab={st['dp_abandoned']} "
                     f"survive={st['dp_survivors']})"))
        if quick:
            # cascade smoke (verify.sh --quick): the exact DTW path must be
            # exact and every cascade stage must actually fire
            assert recall_e == 1.0, f"DTW exact recall {recall_e} != 1.0"
            assert st["considered"] > 0 and st["dp_survivors"] > 0, st
            assert st["killed_lb_keogh"] + st["killed_lb_improved"] > 0, st
        if B == max(batches) and not quick:
            # candidate-ordering shoot-out (Metric.order): which strategy
            # wins at serving batch — recorded so the default is auditable
            from repro.core.metric import ORDERS
            rec_b["dtw_order_qps"] = {}
            for order in ORDERS:
                t_o = _time(lambda: exact_search_device_batch(
                    idx, qs, K, metric="dtw", order=order), repeat=1)
                rec_b["dtw_order_qps"][order] = B / t_o
                rows.append((f"batch_search/dtw_order/{order}/B{B}",
                             B / t_o, "qps"))
        for nbr in sweep:
            t_ext = _time(lambda: extended_search_device_batch(
                idx, qs, K, nbr=nbr, rerank=False, metric="dtw"), repeat=1)
            ids, _, _ = extended_search_device_batch(idx, qs, K, nbr=nbr,
                                                     rerank=False,
                                                     metric="dtw")
            recall = float(np.mean(
                [len(gt[i] & set(ids[i][ids[i] >= 0].tolist())) / K
                 for i in range(B)]))
            rec_b[f"qps_dtw_extended_nbr{nbr}"] = B / t_ext
            rec_b[f"recall_dtw_extended_nbr{nbr}"] = recall
            rows.append((f"batch_search/dtw_extended/B{B}/nbr{nbr}",
                         B / t_ext, f"qps;recall@{K}={recall:.3f}"))


def run(n: int = common.N_SERIES, length: int = common.LENGTH,
        out_json: str = OUT_JSON, quick: bool = False, metric: str = "both"
        ) -> list[tuple[str, float, str]]:
    batches = (8,) if quick else BATCHES
    if quick:
        n, length = min(n, 4000), min(length, 64)
    rows: list[tuple[str, float, str]] = []
    record: dict = {"k": K, "batches": {}}
    sweep = NBR_SWEEP[:2] if quick else NBR_SWEEP

    if metric in ("ed", "both"):        # the ED collection is the expensive
        db = common.dataset("rand", n=n, length=length)   # build: skip it
        idx = DumpyIndex.build(db, common.params())       # for --metric dtw
        record.update(n_series=n, length=length,
                      n_leaves=int(idx.flat.n_leaves))
    for B in batches if metric in ("ed", "both") else ():
        qs = random_walks(B, length, seed=9000 + B)
        gt = [set(brute_force_knn(db, q, K)[0].tolist()) for q in qs]

        t_loop = _time(lambda: [exact_search_device(idx, q, K) for q in qs],
                       repeat=1)
        t_batch = _time(lambda: exact_search_device_batch(idx, qs, K))
        t_approx = _time(lambda: approximate_search_device_batch(idx, qs, K))

        qps_loop = B / t_loop
        qps_batch = B / t_batch
        qps_approx = B / t_approx
        speedup = qps_batch / qps_loop
        record["batches"][str(B)] = {
            "qps_exact_loop": qps_loop, "qps_exact_batch": qps_batch,
            "qps_approx_batch": qps_approx, "exact_speedup": speedup,
        }
        rows.append((f"batch_search/exact_loop/B{B}", qps_loop, "qps"))
        rows.append((f"batch_search/exact_batch/B{B}", qps_batch,
                     f"qps;speedup={speedup:.1f}x"))
        rows.append((f"batch_search/approx_batch/B{B}", qps_approx, "qps"))

        # extended search (Alg. 4): recall vs QPS as the nbr budget widens —
        # the serving operating-point curve (device path, no host re-rank)
        for nbr in sweep:
            t_ext = _time(lambda: extended_search_device_batch(
                idx, qs, K, nbr=nbr, rerank=False))
            ids, _, _ = extended_search_device_batch(idx, qs, K, nbr=nbr,
                                                     rerank=False)
            recall = float(np.mean(
                [len(gt[i] & set(ids[i][ids[i] >= 0].tolist())) / K
                 for i in range(B)]))
            qps_ext = B / t_ext
            record["batches"][str(B)][f"qps_extended_nbr{nbr}"] = qps_ext
            record["batches"][str(B)][f"recall_extended_nbr{nbr}"] = recall
            rows.append((f"batch_search/extended/B{B}/nbr{nbr}", qps_ext,
                         f"qps;recall@{K}={recall:.3f}"))

    if metric in ("dtw", "both"):
        _run_dtw(record, rows, batches, sweep, quick)

    # quick mode is a smoke run on a smaller problem: deltas vs the committed
    # full-size baseline would be meaningless, and it must not overwrite it
    if not quick:
        _report_deltas(record, _load_previous(out_json), rows)
        if metric == "both":            # partial sweeps must not clobber it
            with open(out_json, "w") as fh:
                json.dump(record, fh, indent=1)
    return rows


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke run (no baseline update)")
    ap.add_argument("--metric", choices=("ed", "dtw", "both"),
                    default="both",
                    help="which metric sweep(s) to run (baseline JSON is "
                         "only written by the full 'both' run)")
    args = ap.parse_args()
    for name, val, note in run(quick=args.quick, metric=args.metric):
        print(f"{name:40s} {val:12.1f} {note}")
    if not args.quick and args.metric == "both":
        print(f"wrote {OUT_JSON}")
