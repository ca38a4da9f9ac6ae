"""Index building: host vs device backend timing + Table 1 structure stats.

Two sections:

* **backend** — wall-clock of ``DumpyIndex.build`` with the host backend
  (reference Alg. 1 recursion) vs the device backend (bottom-up grouped
  build, ``core/build_device.py``) at each scale, with the layout-parity
  check (``flat.order`` / ``leaf_offsets`` equality) asserted inline.  The
  device build is jit-warmed on a small slice first so compilation is
  excluded (builds are rare, long-lived programs).
* **table1** (full runs only) — the paper's Fig. 7 + Table 1 comparison of
  Dumpy vs TARDIS / iSAX2+ / DSTree structure statistics.

Emits ``BENCH_build.json`` next to the repo root and, when a previous run's
file exists, prints build-time deltas against it — with a loud warning on
any >10% build-time regression — mirroring ``bench_batch_search``.

    PYTHONPATH=src python -m benchmarks.bench_build            # full
    PYTHONPATH=src python -m benchmarks.bench_build --quick    # smoke

``--quick`` is a seconds-scale smoke (20k×128 backend compare only) wired
into ``scripts/verify.sh``; it exercises both backends and the parity check
but does not overwrite the committed baseline JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

from repro.core.index import DumpyIndex
from . import common

QUICK_SCALES = ((20_000, 128),)
FULL_SCALES = ((20_000, 128), (200_000, 128))
REGRESSION_TOL = 0.10           # warn when build time grows by more than this
OUT_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_build.json")


def _load_previous(out_json: str) -> dict | None:
    try:
        with open(out_json) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def _bench_backends(rows: list, record: dict, scales) -> None:
    p = common.params()
    for n, length in scales:
        db = common.dataset("rand", n=n, length=length)
        # warm the device build's jitted stages on a slice: compile time is
        # not part of the steady-state build cost being tracked
        DumpyIndex.build(db[: min(n, 2000)], p, backend="device")
        t0 = time.perf_counter()
        dev = DumpyIndex.build(db, p, backend="device")
        jax.block_until_ready(dev.flat.order)   # async dispatch: sync window
        t_dev = time.perf_counter() - t0
        t0 = time.perf_counter()
        host = DumpyIndex.build(db, p)
        t_host = time.perf_counter() - t0
        parity = (np.array_equal(host.flat.order, dev.flat.order)
                  and np.array_equal(host.flat.leaf_offsets,
                                     dev.flat.leaf_offsets))
        speedup = t_host / t_dev
        key = f"{n}x{length}"
        record["scales"][key] = {
            "t_host_s": t_host, "t_device_s": t_dev, "speedup": speedup,
            "parity": bool(parity), "n_leaves": int(host.flat.n_leaves),
        }
        note = (f"host={t_host:.2f}s;device={t_dev:.2f}s;"
                f"speedup={speedup:.1f}x;parity={parity}")
        rows.append((f"build/backend/{key}", t_dev * 1e6, note))
        if not parity:
            print(f"WARNING: backend layout parity FAILED at {key}",
                  file=sys.stderr)


def _report_deltas(record: dict, prev: dict | None, rows: list) -> int:
    """Build-time delta rows vs the previous run; returns #regressions."""
    if not prev or "scales" not in prev:
        rows.append(("build/delta", 0.0, "no previous baseline"))
        return 0
    regressions = 0
    for key, cur in record["scales"].items():
        old = prev["scales"].get(key)
        if not old:
            continue
        for field in ("t_host_s", "t_device_s"):
            if not old.get(field) or field not in cur:
                continue
            delta = cur[field] / old[field] - 1.0
            note = f"{delta:+.1%} vs previous"
            if delta > REGRESSION_TOL:
                regressions += 1
                note += (f"  ** WARNING: >{REGRESSION_TOL:.0%} build-time "
                         f"regression **")
                print(f"WARNING: {field}/{key} regressed {delta:+.1%} "
                      f"({old[field]:.2f}s -> {cur[field]:.2f}s)",
                      file=sys.stderr)
            rows.append((f"build/delta/{field}/{key}", 100.0 * delta, note))
    return regressions


def _table1(rows: list) -> None:
    """Paper Fig. 7 + Table 1: structure statistics across index families.

    The original's build time is disk-I/O-bound (random writes); in this
    in-core JAX setting the I/O term is the leaf count (≈ write
    granularity), reported as ``derived``."""
    for ds in ("rand", "skew"):
        db = common.dataset(ds)
        built = common.build_all(db, common.params())
        for name, (idx, dt) in built.items():
            if name == "dstree":
                stats = (f"leaves={idx.n_leaves};nodes={idx.n_nodes};"
                         f"height={idx.height};fill={idx.fill_factor:.3f}")
            else:
                s = idx.stats
                stats = (f"leaves={s.n_leaves};nodes={s.n_nodes};"
                         f"height={s.height};fill={s.fill_factor:.3f}")
            rows.append((f"build/{ds}/{name}", dt * 1e6, stats))


def run(quick: bool = False, out_json: str = OUT_JSON
        ) -> list[tuple[str, float, str]]:
    rows: list[tuple[str, float, str]] = []
    record: dict = {"scales": {}}
    _bench_backends(rows, record, QUICK_SCALES if quick else FULL_SCALES)
    if not quick:
        _table1(rows)
        # quick mode is a smoke on the small scale only: deltas vs the
        # committed full baseline would be partial, and it must not
        # overwrite it
        _report_deltas(record, _load_previous(out_json), rows)
        with open(out_json, "w") as fh:
            json.dump(record, fh, indent=1)
    return rows


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
