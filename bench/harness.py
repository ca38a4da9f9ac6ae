"""Run one cell once: find its files by name, set up, measure, check, report.

Everything a cell is made of is a file found by the names in
``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the deployment (collection, index
  parameters, guarantees, where it comes from);
* ``bench/traffic/<traffic>.json``: the mix, whose ``kind`` names its
  driver, ``bench/drivers/<kind>.py``, and whose ``limits`` hold the limit
  of each number the correctness check compares;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A driver's ``run(ctx)`` sets up (timed into ``setup_s``), measures inside
``ctx.window()`` and returns an :class:`Outcome`. The harness then reads the
device's peak memory, frees every device array, runs the outcome's check
against the plain reference, and prints the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Callable

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list
    root: str = ROOT


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config, traffic = load_files(w["config"], w["traffic"], root)
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    return Cell(name, config, traffic, int(w["chips"]), e2e, per_layer,
                root)


def load_files(config: str, traffic: str, root: str = ROOT):
    """A configuration and a traffic mix, by name."""
    bench = os.path.join(root, "bench")
    return (_json(os.path.join(bench, "configs", config + ".json")),
            _json(os.path.join(bench, "traffic", traffic + ".json")))


def driver(kind: str, root: str = ROOT):
    """The driver module of a traffic kind, ``bench/drivers/<kind>.py``."""
    return _module(os.path.join(root, "bench", "drivers", kind + ".py"),
                   f"bench_driver_{kind}")


def reader(metric: str, root: str = ROOT):
    """The reader of a per-layer metric, ``bench/metrics/<metric>.py``."""
    return _module(os.path.join(root, "bench", "metrics", metric + ".py"),
                   "bench_metric_" + metric.replace(".", "_"))


def _module(path: str, modname: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Outcome:
    """What a driver hands back once its window has closed. ``check(control)``
    runs after every device array is freed and returns the readings the
    limits are applied to; ``control=True`` puts the reference, in the
    precision below the configuration's, in the program's place."""
    metrics: dict
    attempted: int
    failed: int
    counters: dict
    check: Callable[[bool], dict]


class Context:
    """Per-run state a driver uses: the cell, the seed, the window length,
    the set-up clock, and the traced window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.timers: dict = {}
        self.setup_s: float | None = None
        self.window_s: float | None = None
        self.trace_summary: dict | None = None
        self.trace_dir = os.path.join(cell.root, ".bench_trace")

    @contextlib.contextmanager
    def timed(self, name: str):
        """Host-clock a set-up stage into ``timers[name]``; the stage must
        end in ``block_until_ready`` itself."""
        t = time.perf_counter()
        yield
        self.timers[name] = time.perf_counter() - t

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts; with tracing on,
        the profiler runs around it and ``bench.window`` marks it."""
        import jax
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield t0
        finally:
            self.window_s = time.perf_counter() - t0
            if self.trace:
                jax.profiler.stop_trace()


def _free_device() -> None:
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def _finite(v):
    return v if v is None or np.isfinite(v) else str(v)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, control: bool = False, chip: bool = True):
    """One run of ``cell``: ``(result, outcome)``, the result object and the
    driver's :class:`Outcome`, whose ``check`` can be read again afterwards
    (for the control). ``chip=False`` skips the look for an accelerator
    (tests on the CPU)."""
    import jax
    devices = jax.devices()
    if chip and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} device(s)")
    used = devices[:cell.chips]
    ctx = Context(cell, seed, seconds, trace, t_start)
    out: Outcome = driver(cell.traffic["kind"], cell.root).run(ctx)
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in used), default=0)
    if trace:
        from bench import trace_reduce
        ctx.trace_summary = trace_reduce.reduce_dir(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    _free_device()
    readings = out.check(control)
    metrics = {**out.metrics, "setup_s": ctx.setup_s}
    from bench.check import verdict
    correct, rows = verdict(readings, cell.traffic["limits"])

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(out.attempted),
              "failed": int(out.failed)}
    if trace:
        s = ctx.trace_summary
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        rd = {"ctx": ctx, "counters": out.counters, "trace": s,
              "metrics": metrics, "device": device}
        vals = {}
        for m in cell.per_layer:
            v = reader(m["name"], cell.root).read(rd)
            if v is not None:
                vals[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = vals
        result["breakdown"] = {"device_ops": s["device_ops"],
                               "idle_gaps": s["idle_gaps"]}
    else:
        missing = [m["name"] for m in cell.end_to_end if m["name"] not in metrics]
        if missing:
            raise RuntimeError(f"driver gave no {missing}")
        result["metrics"] = {m["name"]: {"value": float(metrics[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = [{"name": n, "value": _finite(v), "limit": lim}
                        for n, v, lim in rows]
    return result, out


def prepare() -> None:
    """Start-up shared by the entry points: the program under ``src/`` on
    the path, and JAX's persistent compilation cache on for every program,
    however quick to compile, so that only a cell's first run compiles."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(args, t_start: float) -> int:
    cell = load_cell(args.workload)
    prepare()
    try:
        result, _ = run_cell(cell, args.seed, args.seconds,
                             bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for c in result["checks"]:
        print(f"check {c['name']} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
