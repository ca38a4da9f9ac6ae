"""Profiler trace -> device busy time, idle share, top operations, idle gaps.

A run with ``--trace 1`` records one ``jax.profiler`` trace around its
measured window and marks the window, and what the harness does inside it,
with ``TraceAnnotation`` spans named ``bench.*``. This module reads the
``.xplane.pb`` with ``jax.profiler.ProfileData`` alone:

* device operations: on a TPU the ``XLA Ops`` line of every
  ``/device:TPU:<i>`` plane; on the CPU (tests only) the XLA client
  threads of the ``/host:CPU`` plane;
* host spans: every event named ``bench.*`` on any host thread.

``reduce`` clips the operations to the ``bench.window`` span. Busy time is
the union of the operations' intervals, averaged over the devices; an idle
gap is a stretch of the window with no operation on a device, named after
the harness span that overlaps it most (the shorter span on a tie), or
``unattributed``.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW = "bench.window"
TOP = 10


def load(trace_dir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def device_ops(pd) -> list[list[tuple[str, float, float]]]:
    """Operation intervals ``(name, start_ns, end_ns)``, one list per
    device."""
    out = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            if not lines:
                raise ValueError(f"{plane.name} has no 'XLA Ops' line: "
                                 f"{[ln.name for ln in plane.lines]}")
            out.append([ev for ln in lines for ev in _events(ln)])
    if out:
        return out
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            evs = [ev for ln in plane.lines
                   if ln.name.startswith("tf_XLAPjRtCpuClient")
                   for ev in _events(ln)
                   if ev[2] > ev[1]
                   and not ev[0].startswith(("Threadpool", "ThunkExecutor"))]
            return [evs]
    return []


def host_spans(pd, prefix: str = "bench.") -> list[tuple[str, float, float]]:
    return [ev for plane in pd.planes if plane.name.startswith("/host")
            for ln in plane.lines for ev in _events(ln)
            if ev[0].startswith(prefix)]


def _self_times(ops, totals: dict) -> None:
    """Add each operation's self time (its duration less that of the
    operations nested in it, as a ``while`` holds its body's fusions) to
    ``totals[name]``, in seconds."""
    stack: list[list] = []                     # [name, start, end, nested]

    def close(top):
        totals[top[0]] = totals.get(top[0], 0.0) + \
            (top[2] - top[1] - top[3]) * 1e-9

    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([n, s, e, 0.0])
    while stack:
        close(stack.pop())


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _name_gap(g0: float, g1: float, starts, ends, names) -> str:
    ov = np.minimum(ends, g1) - np.maximum(starts, g0)
    if not len(ov) or ov.max() <= 0:
        return "unattributed"
    # the largest overlap; on a tie the shorter span
    best = np.lexsort((ends - starts, -ov))[0]
    return names[best]


def reduce(ops_per_device: list, spans: list) -> dict:
    """``busy_s``, ``window_s``, ``idle_share``, ``device_ops`` (the
    operations with the most self time, summed over the window, in
    seconds) and ``idle_gaps`` (the longest gaps, named)."""
    win = [(s, e) for name, s, e in spans if name == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(win)}")
    w0, w1 = win[0]
    inner = [sp for sp in spans if sp[0] != WINDOW]
    busy, totals, gaps = [], {}, []
    for ops in ops_per_device:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        _self_times(clipped, totals)
        u = _union([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in u) * 1e-9)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps.extend((g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                    if g1 > g0)
    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy) / len(busy) if busy else 0.0
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: g[0] - g[1])
    names = [n for n, _, _ in inner]
    starts = np.array([s for _, s, _ in inner])
    ends = np.array([e for _, _, e in inner])
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[_name_gap(g0, g1, starts, ends, names),
                           (g1 - g0) * 1e-9] for g0, g1 in gaps[:TOP]]}


def reduce_dir(trace_dir: str) -> dict:
    pd = load(trace_dir)
    return reduce(device_ops(pd), host_spans(pd))
