"""Open-loop serving benchmark for the continuous-batching front-end
(``repro.serving.batching``, docs/serving.md).

``bench_batch_search`` is closed-loop: it hands the device fixed 64-wide
batches and measures steady-state QPS.  This benchmark drives the
:class:`CoalescingFrontend` the way serving traffic actually arrives — a
Poisson process of single requests with mixed per-request ``k``/``nbr``
knobs — at several offered rates expressed as fractions of the committed
closed-loop baseline (``BENCH_batch_search.json``:
``batches.64.qps_extended_nbr4``, the same index family and metric).

Arrival times are scheduled up front and latency is measured from the
*scheduled* arrival, not the submit call — the open-loop discipline that
avoids coordinated omission (a slow server cannot slow the clock down).
Per rate it reports sustained QPS, p50/p99/p99.9 latency, padding waste and
the bucket-occupancy histogram; a small mixed ED/DTW section runs on a
DP-scaled collection.  The headline acceptance number is the saturation
ratio: best sustained QPS across rates over the closed-loop batch-64
baseline (target ≥ 0.8× — the coalescing/padding/Python overhead budget).

Emits ``BENCH_serving.json`` at the repo root and prints deltas against the
previous run — warning loudly when QPS drops or p99 rises by >10%.

    PYTHONPATH=src python -m benchmarks.bench_serving            # full
    PYTHONPATH=src python -m benchmarks.bench_serving --quick    # smoke

``--quick`` is the seconds-scale smoke wired into ``scripts/verify.sh``:
small collection, two rates, and it *asserts* the front-end actually
coalesced (mean occupancy > 1 at the top rate) and that p99 stays under a
loose budget — without touching the committed baseline JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core.index import DumpyIndex
from repro.core.search_device import extended_search_device_batch
from repro.data.series import random_walks
from repro.serving.batching import CoalescingFrontend
from . import common

K_MAX = 10
NBR_MAX = 4
MAX_BATCH = 64
MAX_WAIT = 0.002
#: offered load as fractions of the closed-loop baseline; the top rate is
#: past capacity on purpose — that run measures saturation throughput
RATE_FRACS = (0.25, 0.6, 1.0, 1.4)
SATURATION_TARGET = 0.8         # sustained/closed-loop ratio floor
REGRESSION_TOL = 0.10
QUICK_P99_BUDGET = 0.25         # seconds; loose smoke bound
MIX_N, MIX_LEN = 4000, 64       # DP-scaled mixed-metric collection
OUT_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serving.json")
BATCH_JSON = os.path.join(os.path.dirname(OUT_JSON),
                          "BENCH_batch_search.json")

#: the serving knob mix: per-request k/nbr cycle (metric fixed per section)
KNOB_MIX = ((5, 1), (10, 4), (10, 2), (5, 4), (10, 1), (5, 2))


def _closed_loop_baseline(idx, qs64) -> tuple[float, str]:
    """The committed closed-loop batch-64 extended QPS, or an inline
    measurement when the committed file predates this benchmark's shapes."""
    try:
        with open(BATCH_JSON) as fh:
            rec = json.load(fh)
        if rec.get("n_series") == idx.db.shape[0] \
                and "64" in rec.get("batches", {}):
            qps = rec["batches"]["64"][f"qps_extended_nbr{NBR_MAX}"]
            return float(qps), "BENCH_batch_search.json"
    except (OSError, ValueError, KeyError):
        pass
    fn = lambda: extended_search_device_batch(idx, qs64, K_MAX, nbr=NBR_MAX,
                                              rerank=False)
    fn()                                # warm: compile is not steady state
    _, dt = common.timed(fn, repeat=3)
    return 64 / dt, "inline"


def _open_loop(fe: CoalescingFrontend, pool: np.ndarray, rate: float,
               n_req: int, mix, seed: int) -> dict:
    """Drive one Poisson arrival schedule through ``fe`` and summarize.

    Latency is ``t_done - scheduled_arrival``: if the generator falls
    behind (server saturated), requests submit late but the clock charges
    the server, not the schedule."""
    # lint: allow-timing (open-loop host clock; device sync is inside the
    # frontend's harvest)
    rng = np.random.default_rng(seed)
    sched = time.perf_counter() + 0.005 + np.cumsum(
        rng.exponential(1.0 / rate, size=n_req))
    futs = []
    for i in range(n_req):
        now = time.perf_counter()
        if sched[i] > now:
            time.sleep(sched[i] - now)
        k, nbr, met = mix[i % len(mix)]
        futs.append(fe.submit(pool[i % len(pool)], k=k, nbr=nbr, metric=met))
    lat = np.empty(n_req)
    t_last = 0.0
    for i, f in enumerate(futs):
        r = f.result(timeout=300)
        lat[i] = r.t_done - sched[i]
        t_last = max(t_last, r.t_done)
    s = fe.stats
    return {
        "offered_qps": rate, "n_requests": n_req,
        "sustained_qps": n_req / (t_last - sched[0]),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "p999_ms": float(np.percentile(lat, 99.9) * 1e3),
        "mean_ms": float(lat.mean() * 1e3),
        "padding_waste": round(s.padding_waste, 4),
        "mean_occupancy": round(s.mean_occupancy, 3),
        "batches": s.batches, "failed": s.failed,
        "occupancy": {str(b): c for b, c in sorted(s.occupancy.items())},
    }


def _report_deltas(record: dict, prev: dict | None, rows: list) -> int:
    """QPS-down / latency-up deltas vs the previous BENCH_serving.json."""
    if not prev or "rates" not in prev:
        rows.append(("serving/delta", 0.0, "no previous baseline"))
        return 0
    regressions = 0
    checks = [("sustained_qps", -1), ("p50_ms", +1), ("p99_ms", +1),
              ("p999_ms", +1)]
    for frac, cur in record["rates"].items():
        old = prev["rates"].get(frac)
        if not old:
            continue
        for key, direction in checks:
            if key not in old or not old[key] or key not in cur:
                continue
            delta = cur[key] / old[key] - 1.0
            note = f"{delta:+.1%} vs previous"
            if delta * direction > REGRESSION_TOL:
                regressions += 1
                kind = "latency" if direction > 0 else "QPS"
                note += f"  ** WARNING: >{REGRESSION_TOL:.0%} {kind} " \
                        f"regression **"
                print(f"WARNING: serving {key}@{frac} regressed {delta:+.1%} "
                      f"({old[key]:.2f} -> {cur[key]:.2f})", file=sys.stderr)
            rows.append((f"serving/delta/{key}/{frac}", 100.0 * delta, note))
    old_sat = prev.get("saturation", {}).get("ratio_vs_closed_loop")
    new_sat = record["saturation"]["ratio_vs_closed_loop"]
    if old_sat:
        delta = new_sat / old_sat - 1.0
        if delta < -REGRESSION_TOL:
            regressions += 1
            print(f"WARNING: saturation ratio regressed {delta:+.1%}",
                  file=sys.stderr)
        rows.append(("serving/delta/saturation", 100.0 * delta,
                     f"{delta:+.1%} vs previous"))
    return regressions


def _run_mixed_metric(record: dict, rows: list, quick: bool) -> None:
    """Mixed ED/DTW traffic through one front-end on a DP-scaled collection
    (every 4th request warps; the bucket program blends the metric per
    lane — this section proves the mix serves at one program per bucket)."""
    n = 1500 if quick else MIX_N
    db = common.dataset("rand", n=n, length=MIX_LEN)
    idx = DumpyIndex.build(db, common.params())
    pool = random_walks(64, MIX_LEN, seed=77).astype(np.float32)
    mix = [(10, 2, "ed"), (5, 4, "dtw"), (10, 1, "ed"), (5, 2, "ed")]
    # a bucket holding any DTW lane pays the band-DP gather for the whole
    # candidate mask, so mixed traffic serves at DTW-ish rates (see the
    # committed qps_dtw_extended_nbr4) — keep the offered load below that
    rate, n_req = (25.0, 50) if quick else (40.0, 200)
    with CoalescingFrontend(idx, k_max=K_MAX, nbr_max=NBR_MAX,
                            max_batch=MAX_BATCH, max_wait=MAX_WAIT) as fe:
        res = _open_loop(fe, pool, rate, n_req, mix, seed=5)
    record["mixed_metric"] = {"n_series": n, "length": MIX_LEN,
                              "dtw_fraction": 0.25, **res}
    rows.append(("serving/mixed_metric", res["sustained_qps"],
                 f"qps;p99={res['p99_ms']:.1f}ms;"
                 f"occ={res['mean_occupancy']:.2f}"))
    assert res["failed"] == 0, "mixed-metric section had failed requests"


def run(n: int = common.N_SERIES, length: int = common.LENGTH,
        out_json: str = OUT_JSON, quick: bool = False
        ) -> list[tuple[str, float, str]]:
    if quick:
        n, length = min(n, 4000), min(length, 64)
    rows: list[tuple[str, float, str]] = []
    db = common.dataset("rand", n=n, length=length)
    idx = DumpyIndex.build(db, common.params())
    pool = random_walks(256, length, seed=31).astype(np.float32)

    base_qps, base_src = _closed_loop_baseline(idx, pool[:64])
    record: dict = {
        "k_max": K_MAX, "nbr_max": NBR_MAX, "max_batch": MAX_BATCH,
        "max_wait": MAX_WAIT, "n_series": n, "length": length,
        "n_leaves": int(idx.flat.n_leaves),
        "knob_mix": [list(m) for m in KNOB_MIX],
        "baseline": {"qps_closed_loop_b64": base_qps, "source": base_src},
        "rates": {},
    }
    rows.append(("serving/closed_loop_b64", base_qps, f"qps ({base_src})"))

    fracs = (0.3, 1.2) if quick else RATE_FRACS
    mix = [(k, nbr, "ed") for k, nbr in KNOB_MIX]
    best = 0.0
    for frac in fracs:
        rate = max(base_qps * frac, 20.0)
        n_req = int(min(1500, max(200, rate * 1.2)))
        if quick:
            n_req = min(n_req, 300)
        # fresh front-end per rate: per-rate occupancy/waste, shared jit cache
        with CoalescingFrontend(idx, k_max=K_MAX, nbr_max=NBR_MAX,
                                max_batch=MAX_BATCH, max_wait=MAX_WAIT) as fe:
            res = _open_loop(fe, pool, rate, n_req, mix,
                             seed=int(frac * 1000))
        record["rates"][f"{frac}x"] = res
        best = max(best, res["sustained_qps"])
        rows.append((f"serving/open_loop/{frac}x", res["sustained_qps"],
                     f"qps;p50={res['p50_ms']:.1f}ms;p99={res['p99_ms']:.1f}"
                     f"ms;p99.9={res['p999_ms']:.1f}ms;"
                     f"occ={res['mean_occupancy']:.2f};"
                     f"waste={res['padding_waste']:.0%}"))
        assert res["failed"] == 0, f"rate {frac}x had failed requests"

    ratio = best / base_qps
    record["saturation"] = {"sustained_qps": best,
                            "ratio_vs_closed_loop": ratio}
    rows.append(("serving/saturation_ratio", 100.0 * ratio,
                 f"% of closed-loop b64 (target >= "
                 f"{SATURATION_TARGET:.0%})"))
    if ratio < SATURATION_TARGET:
        print(f"WARNING: saturation {ratio:.1%} below the "
              f"{SATURATION_TARGET:.0%} target", file=sys.stderr)

    _run_mixed_metric(record, rows, quick)

    if quick:
        # verify.sh smoke: the front-end must actually coalesce under load
        # and keep tail latency sane on the small collection
        top = record["rates"][f"{fracs[-1]}x"]
        assert top["mean_occupancy"] > 1.0, \
            f"no coalescing at the top rate: {top}"
        assert top["p99_ms"] < QUICK_P99_BUDGET * 1e3, \
            f"quick p99 {top['p99_ms']:.1f}ms over budget: {top}"
    else:
        _report_deltas(record, _load_previous(out_json), rows)
        with open(out_json, "w") as fh:
            json.dump(record, fh, indent=1)
    return rows


def _load_previous(out_json: str) -> dict | None:
    try:
        with open(out_json) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke run (no baseline update)")
    args = ap.parse_args()
    for name, val, note in run(quick=args.quick):
        print(f"{name:40s} {val:12.1f} {note}")
    if not args.quick:
        print(f"wrote {OUT_JSON}")
