"""Read the numbers ``correct`` compares, for the program and for its
control, on several seeds in one process, at a cell's own size and load.

    python3 -m bench.control --workload <name> --seeds 1,2,3 --seconds <s>

For each seed: one ordinary run of the cell (set-up, window, check), then
the same check with the control in the program's place: the plain
reference computed in bfloat16, a precision below the configuration's
float32. Prints one JSON line per seed with both readings. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness
    from bench.check import verdict
    harness.prepare()
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            result, out = harness.run_cell(cell, seed, args.seconds, False,
                                           time.perf_counter())
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        ctrl = out.check(True)
        ok, rows = verdict(ctrl, cell.traffic["limits"])
        print(json.dumps({"seed": seed, "program": result["checks"],
                          "program_correct": result["correct"],
                          "metrics": result["metrics"],
                          "control": {n: float(v) for n, v, _ in rows},
                          "control_correct": ok}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
