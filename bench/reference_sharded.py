"""The plain reference (``bench.reference``) over a collection that no
one device holds: each shard is made on its own device by ``make_shard``
(the collection regenerated from the seed, ``bench.data_shards``), and
the reference runs there.

``knn`` takes each shard's exact top-k (``reference.knn``), offsets its
ids by the shard's first row, and merges the shards' lists on the host by
(direct distance, id): the exact top-k of the whole collection.
``distances`` scores each id on the shard that holds it.  Like
``bench.reference`` it imports nothing of the program under test.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import reference


def knn(make_shard, n_shards: int, qs: np.ndarray, k: int,
        dtype=jnp.float32):
    """Host ``(ids [Q, k], d [Q, k])`` over the ``n_shards`` equal shards
    ``make_shard(s) -> [R, n]``, in ``dtype`` as ``reference.knn``."""
    ids, ds = [], []
    for s in range(n_shards):
        x = make_shard(s)
        i, d = reference.knn(x, qs, k, dtype=dtype)
        ids.append(np.where(i >= 0, i + s * x.shape[0], -1))
        ds.append(d)
        x.delete()
    ids, ds = np.concatenate(ids, 1), np.concatenate(ds, 1)
    out_i = np.full((len(qs), k), -1, np.int64)
    out_d = np.full((len(qs), k), np.inf, np.float32)
    for r in range(len(qs)):
        perm = np.lexsort((ids[r], ds[r]))[:k]
        perm = perm[np.isfinite(ds[r][perm])]
        out_i[r, :len(perm)] = ids[r][perm]
        out_d[r, :len(perm)] = ds[r][perm]
    return out_i, out_d


def distances(make_shard, n_shards: int, qs: np.ndarray,
              ids: np.ndarray) -> np.ndarray:
    """float32 distance of each query to each of its ids ``[Q, k]``
    (``-1``: +inf), each scored on the shard that holds it."""
    ids = np.asarray(ids, np.int64)
    out = np.full(ids.shape, np.inf, np.float32)
    for s in range(n_shards):
        x = make_shard(s)
        R = x.shape[0]
        local = np.where((ids >= s * R) & (ids < (s + 1) * R), ids - s * R,
                         -1)
        d = reference.distances(x, qs, local)
        out = np.where(local >= 0, d, out)
        x.delete()
    return out
