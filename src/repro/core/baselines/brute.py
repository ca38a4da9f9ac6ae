"""Brute-force exact kNN — ground truth for every benchmark and test."""
from __future__ import annotations

import numpy as np

from ..lb import dtw_np, ed_np

# rows per ED block: keeps the [block, n] temporaries cache-sized (a whole
# 4M x 256 collection would need two 4 GiB ones per query; on 1M x 256 the
# blocked scan measured ~2x faster on one CPU core) without changing any
# value, and lets threads scan the same collection side by side
BLOCK_ROWS = 1 << 10


def brute_force_knn(db: np.ndarray, q: np.ndarray, k: int,
                    metric: str = "ed", band: int | None = None
                    ) -> tuple[np.ndarray, np.ndarray]:
    if metric == "ed":
        if len(db) > BLOCK_ROWS:
            d = np.concatenate([ed_np(q, db[i:i + BLOCK_ROWS])
                                for i in range(0, len(db), BLOCK_ROWS)])
        else:
            d = ed_np(q, db)
    else:
        band = band or max(1, int(0.1 * db.shape[1]))
        d = np.array([dtw_np(q, x, band) for x in db])
    if k < len(d):
        # the k smallest by (d, id) — what a stable full argsort's first k
        # are — from the candidates at or below the k-th value
        cand = np.flatnonzero(d <= np.partition(d, k - 1)[k - 1])
    else:
        cand = np.arange(len(d))
    idx = cand[np.argsort(d[cand], kind="stable")][:k]
    return idx.astype(np.int64), d[idx].astype(np.float32)
