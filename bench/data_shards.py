"""The *Rand* collection of ``bench.data``, made shard by shard: shard
``s`` of ``S`` on its own device, bitwise rows ``[s·R, (s+1)·R)`` of
``data.walks(seed, stream, N, n)`` (``R = N / S``).

``data.walks`` makes chunk ``i`` of ``CHUNK`` rows from ``fold_in(key,
i)``; a shard is the same chunks, ``i`` in ``[s·R/CHUNK, (s+1)·R/CHUNK)``,
made by one jitted call on the shard's device, so no device ever holds
more than its shard and every shard runs the same program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import data


@functools.partial(jax.jit, static_argnames=("count", "rows", "n"))
def _chunks(k: jax.Array, first: jax.Array, *, count: int, rows: int,
            n: int) -> jax.Array:
    i = first + jnp.arange(count, dtype=jnp.uint32)
    out = jax.lax.map(lambda j: data._walk_chunk(k, j, rows, n), i)
    return out.reshape(count * rows, n)


def shard(seed: int, stream: int, n_series: int, n: int, s: int,
          devices: list) -> jax.Array:
    """Shard ``s`` of ``data.walks(seed, stream, n_series, n)`` split in
    ``len(devices)`` equal row ranges, made on ``devices[s]``."""
    S = len(devices)
    ch = min(n_series, data.CHUNK)
    R = n_series // S
    if n_series % S or R % ch:
        raise ValueError(f"{n_series} rows do not split in {S} shards of "
                         f"whole {ch}-row chunks")
    k = jax.device_put(data.key(seed, stream), devices[s])
    return _chunks(k, np.uint32(s * R // ch), count=R // ch, rows=ch, n=n)


def host_collection(seed: int, stream: int, n_series: int, n: int,
                    devices: list) -> np.ndarray:
    """The whole collection on the host, ``[n_series, n]`` float32: every
    shard made at once, each on its own device, and each copied to the
    host once."""
    parts = [shard(seed, stream, n_series, n, s, devices)
             for s in range(len(devices))]
    out = np.empty((n_series, n), np.float32)
    R = n_series // len(devices)
    for s, x in enumerate(parts):
        out[s * R:(s + 1) * R] = np.asarray(x)
        x.delete()
    return out
