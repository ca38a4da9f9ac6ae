"""The comparison that decides ``correct``.

One number per cell, ``knn_gap``: the widest relative gap, over every
sampled answer and every rank ``r < k``, of

* the served distance at rank ``r`` against the reference's distance at
  rank ``r`` (a missed neighbour, a wrong order or a wrong distance), and
* the served distance against the reference's own distance of the served
  id (an id that does not carry the distance it claims).

An answer reads ``inf`` where it holds a repeated id, or where its
padding (``-1``) does not match the reference: padding is right only
where the reference, too, finds fewer than ``k`` candidates. Ranks are compared by distance, so two ids whose
distances tie to rounding may change places without a gap.
"""
from __future__ import annotations

import numpy as np

TINY = 1e-12


def knn_gap(served_ids: list, served_d: list, ref_d: np.ndarray,
            true_d: np.ndarray) -> float:
    """``served_ids``/``served_d``: one array of ``k_i`` entries per answer;
    ``ref_d [A, K]`` the reference's sorted distances (``K >= k_i``);
    ``true_d [A, K]`` the reference's distance of each served id, with the
    served ids padded to ``K`` by ``-1``."""
    worst = 0.0
    for a, (ids, d) in enumerate(zip(served_ids, served_d)):
        k = len(ids)
        r = ref_d[a, :k].astype(np.float64)
        n = int(np.isfinite(r).sum())      # fewer candidates than k: pads
        live = ids >= 0
        if k == 0 or len(d) != k or live.sum() != n or not live[:n].all() \
                or len(np.unique(ids[:n])) != n \
                or not np.isfinite(d[:n]).all():
            return float("inf")
        r, t, d = r[:n], true_d[a, :n].astype(np.float64), \
            d[:n].astype(np.float64)
        if n == 0:
            continue
        rank = np.abs(d - r) / np.maximum(r, TINY)
        claim = np.abs(d - t) / np.maximum(t, TINY)
        worst = max(worst, float(rank.max()), float(claim.max()))
    return worst


def pad_ids(served_ids: list, K: int) -> np.ndarray:
    out = np.full((len(served_ids), K), -1, np.int64)
    for a, ids in enumerate(served_ids):
        out[a, :len(ids)] = ids[:K]
    return out


def verdict(readings: dict, limits: dict) -> tuple[bool, list]:
    """``correct`` and the ``[name, value, limit]`` rows it was read from;
    a missing or non-finite reading fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = readings.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok &= bool(good)
        rows.append([name, v, limit])
    return ok, rows
