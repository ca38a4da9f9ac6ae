"""Work the search programs must do, and the chip's peaks to hold it to.

Every count is the least that the semantics require, from what the run
observed, never from padded shapes, so a share of the roofline computed
from it cannot pass 100%:

* Exact search (``_exact_knn_sharded``) walks one span schedule shared by
  the whole batch and reads each span it walks once for every query. A
  batch must read at least the spans its most demanding query visits
  (``max(spans_visited)``), and query ``q`` must be scored against the rows
  of the ``spans_visited[q]`` spans it visits. Which spans those are is not
  returned, so both counts take the smallest spans of the schedule: a
  lower bound.

A row is ``n`` float32 values; scoring a row is ``n`` multiply-adds.
"""
from __future__ import annotations

import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
F32 = 4


def peaks(device_kind: str, path: str | None = None) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, not a default."""
    with open(path or os.path.join(ROOT, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def exact_work(spans_visited: list, span_rows: np.ndarray, n: int
               ) -> tuple[float, float]:
    """``(bytes, flops)`` of exact calls, one ``spans_visited [Q]`` array
    per call; ``span_rows`` the live rows of every span of the schedule."""
    cum = np.concatenate([[0], np.cumsum(np.sort(np.asarray(span_rows)))])
    nbytes = flops = 0.0
    for vis in spans_visited:
        vis = np.minimum(np.asarray(vis, np.int64), len(cum) - 1)
        nbytes += float(cum[vis.max()]) * n * F32
        flops += float(cum[vis].sum()) * 2 * n
    return nbytes, flops


def roofline_pct(nbytes: float, flops: float, busy_s: float,
                 peak: dict) -> float | None:
    """Least time the chip could take (the larger of the bytes and the
    operations bound) as a percentage of the device's busy time; ``None``
    where nothing was timed."""
    if busy_s <= 0 or (nbytes <= 0 and flops <= 0):
        return None
    least = max(nbytes / peak["hbm_bytes_per_s"],
                flops / peak["bf16_flops_per_s"])
    return 100.0 * least / busy_s
