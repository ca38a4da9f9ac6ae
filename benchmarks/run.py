"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only build,approx,...]

Prints ``name,us_per_call,derived`` CSV (the assignment contract).
"""
from __future__ import annotations

import argparse
import sys
import traceback

MODULES = {
    "build": "benchmarks.bench_build",               # Fig. 7 + Table 1
    "approx": "benchmarks.bench_approx_search",      # Figs. 9/10
    "exact": "benchmarks.bench_exact_search",        # Table 2
    "scalability": "benchmarks.bench_scalability",   # Fig. 8
    "params": "benchmarks.bench_params",             # Figs. 16/17
    "updates": "benchmarks.bench_updates",           # Fig. 18
    "upper_bounds": "benchmarks.bench_upper_bounds", # Fig. 13
    "kernels": "benchmarks.bench_kernels",           # Pallas microbench
    "knn_softmax": "benchmarks.bench_knn_softmax",   # §1 application 3
}


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(MODULES)

    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        import importlib
        try:
            mod = importlib.import_module(MODULES[name])
            for row in mod.run():
                print(f"{row[0]},{row[1]:.1f},{row[2]}", flush=True)
        except Exception:
            failures += 1
            print(f"{name},nan,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
