"""Plain k-nearest-neighbour reference under Euclidean distance.

Independent of the program under test: it imports nothing of it and is
given only the collection (regenerated from the seed by ``bench.data``)
and the queries.

``knn`` is a blocked brute force. Each block of rows ranks every row by
``|x|^2 - 2 q.x`` (the same order as ``|q - x|^2``) at ``HIGHEST``
precision and keeps the best ``k + MARGIN``; the survivors are then scored
by the direct sum ``sum((q - x)^2)`` and ordered by (distance, id). The
margin absorbs the rounding of the ranking form, so the result is the
exact top-k of the direct sum.

``dtype`` is the precision of the whole computation: float32 for the
reference, bfloat16 for the control that must come out not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MARGIN = 16
ROW_BLOCK = 1 << 16
QUERY_BLOCK = 256


def _precision(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


@functools.partial(jax.jit, static_argnames=("keep", "block", "dtype"))
def _select(db: jax.Array, qs: jax.Array, *, keep: int, block: int, dtype):
    """Ids of the ``keep`` rows of ``db`` with the smallest ranking form."""
    N = db.shape[0]
    q = qs.astype(dtype)
    nb = N // block

    def body(carry, b):
        best_s, best_i = carry
        x = jax.lax.dynamic_slice_in_dim(db, b * block, block).astype(dtype)
        xx = (x * x).sum(-1).astype(jnp.float32)
        qx = jnp.dot(q, x.T, precision=_precision(dtype),
                     preferred_element_type=dtype).astype(jnp.float32)
        s = xx[None, :] - 2.0 * qx
        ids = b * block + jnp.arange(block, dtype=jnp.int32)
        all_s = jnp.concatenate([best_s, s], axis=1)
        all_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, s.shape)], axis=1)
        neg, sel = jax.lax.top_k(-all_s, keep)
        return (-neg, jnp.take_along_axis(all_i, sel, 1)), None

    Q = qs.shape[0]
    init = (jnp.full((Q, keep), jnp.inf, jnp.float32),
            jnp.full((Q, keep), -1, jnp.int32))
    (_, ids), _ = jax.lax.scan(body, init, jnp.arange(nb, dtype=jnp.int32))
    return ids


@functools.partial(jax.jit, static_argnames=("dtype",))
def _direct(db: jax.Array, qs: jax.Array, ids: jax.Array, *, dtype):
    """``sum((q - x)^2)`` in ``dtype`` for ``ids [Q, C]`` (``-1``: +inf)."""
    x = db[jnp.maximum(ids, 0)].astype(dtype)                  # [Q, C, n]
    diff = x - qs.astype(dtype)[:, None, :]
    d2 = (diff * diff).sum(-1).astype(jnp.float32)
    return jnp.where(ids < 0, jnp.inf, d2)


def _order(ids: np.ndarray, d2: np.ndarray, k: int):
    """Per row: the ``k`` smallest by (distance, id), as (ids, distances)."""
    Q = ids.shape[0]
    out_i = np.full((Q, k), -1, np.int64)
    out_d = np.full((Q, k), np.inf, np.float32)
    for r in range(Q):
        perm = np.lexsort((ids[r], d2[r]))[:k]
        perm = perm[np.isfinite(d2[r][perm])]
        out_i[r, :len(perm)] = ids[r][perm]
        out_d[r, :len(perm)] = np.sqrt(d2[r][perm])
    return out_i, out_d


def knn(db: jax.Array, qs: np.ndarray, k: int, dtype=jnp.float32):
    """Exact top-``k`` of every query over the whole collection ``db [N, n]``
    (a device array; ``N`` a multiple of the row block or smaller than it).
    Returns host ``(ids [Q, k], d [Q, k])``."""
    N = db.shape[0]
    block = min(ROW_BLOCK, N)
    if N % block:
        raise ValueError(f"{N} rows is not a multiple of {block}")
    keep = min(k + MARGIN, N)
    outs_i, outs_d = [], []
    for s in range(0, len(qs), QUERY_BLOCK):
        q = jnp.asarray(qs[s:s + QUERY_BLOCK], jnp.float32)
        ids = _select(db, q, keep=keep, block=block, dtype=dtype)
        d2 = _direct(db, q, ids, dtype=dtype)
        i, d = _order(np.asarray(ids), np.asarray(d2), k)
        outs_i.append(i)
        outs_d.append(d)
    return np.concatenate(outs_i), np.concatenate(outs_d)


def distances(db: jax.Array, qs: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float32 distance of each query to each of its ids ``[Q, k]``
    (``-1``: +inf), by the direct sum."""
    out = []
    for s in range(0, len(qs), QUERY_BLOCK):
        q = jnp.asarray(qs[s:s + QUERY_BLOCK], jnp.float32)
        i = jnp.asarray(np.asarray(ids[s:s + QUERY_BLOCK], np.int32))
        out.append(np.sqrt(np.asarray(_direct(db, q, i, dtype=jnp.float32))))
    return np.concatenate(out)
