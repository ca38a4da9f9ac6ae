"""The paper's *Rand* collection, generated on the device from ``--seed``.

Rand (Dumpy, SIGMOD'23, section 7) is a set of z-normalised Gaussian random
walks: the cumulative sum of N(0, 1) steps, shifted to mean 0 and scaled to
standard deviation 1, the same semantics as ``random_walks`` of the program
under test (not the same numbers: the bits come from JAX's threefry).

Streams: the collection is stream 0 of a seed, query pools are streams 1
and up, so queries are held out of the collection. Every array is made by
one jitted call, in chunks of ``CHUNK`` rows inside a ``lax.map`` so the
peak is the output plus one chunk; the same call on the same seed gives the
same bits, which is how the reference regenerates the collection after the
program's state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 16


def key(seed: int, stream: int) -> jax.Array:
    """A threefry key for ``(seed, stream)``; any whole ``seed``, also one
    past 32 or 64 bits, maps to its own key."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), int(stream)])
    return jax.random.wrap_key_data(
        jnp.asarray(ss.generate_state(2, np.uint32)), impl="threefry2x32")


def _walk_chunk(k: jax.Array, i: jax.Array, rows: int, n: int) -> jax.Array:
    steps = jax.random.normal(jax.random.fold_in(k, i), (rows, n), jnp.float32)
    x = jnp.cumsum(steps, axis=1)
    mu = x.mean(axis=1, keepdims=True)
    sd = x.std(axis=1, keepdims=True)
    return (x - mu) / jnp.maximum(sd, 1e-8)


@functools.partial(jax.jit, static_argnames=("rows", "n"))
def _walks(k: jax.Array, rows: int, n: int) -> jax.Array:
    ch = min(rows, CHUNK)
    if rows % ch:
        raise ValueError(f"{rows} rows is not a multiple of {ch}")
    out = jax.lax.map(lambda i: _walk_chunk(k, i, ch, n),
                      jnp.arange(rows // ch, dtype=jnp.uint32))
    return out.reshape(rows, n)


def walks(seed: int, stream: int, rows: int, n: int) -> jax.Array:
    """``[rows, n]`` float32 z-normalised random walks on the default
    device."""
    return _walks(key(seed, stream), rows=rows, n=n)
