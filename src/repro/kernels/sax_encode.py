"""Pallas TPU kernel: fused PAA + SAX symbolization (index-build Stage 1).

Design (TPU v5e target):
* PAA is expressed as a matmul with the segment-averaging matrix
  ``S [n, w]`` (``S[i,j] = w/n`` iff ``i`` in segment ``j``) so it runs on the
  MXU; ``n`` is a multiple of ``w`` and padded to a multiple of 128 by the
  wrapper so both matmul dims are hardware aligned.
* Symbolization compares the PAA block against the breakpoint table in
  chunks of 128 (VPU broadcast-compare + sum), avoiding in-kernel gathers.
* Block shape: ``(block_b, n)`` series per grid step resident in VMEM;
  ``block_b = 256`` with ``n = 1024`` f32 is 1 MB in + ~0.3 MB intermediates,
  well inside the ~16 MB VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sax import breakpoints


def _kernel(x_ref, seg_ref, bp_ref, paa_ref, sax_ref, *, w: int, c: int):
    x = x_ref[...]                                   # (TB, n)
    seg = seg_ref[...]                               # (n, w)
    # HIGHEST: the MXU's default f32 pass rounds operands to bf16, which
    # would shift the PAA by ~0.4% — enough to push the query MINDIST above
    # the true distance and break exact search
    paa = jnp.dot(x, seg, preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)          # (TB, w) MXU
    paa_ref[...] = paa
    # symbolize: count breakpoints <= paa, in chunks of 128 lanes
    bp = bp_ref[...]                                 # (1, c-1) padded to c
    acc = jnp.zeros(paa.shape, jnp.int32)
    n_chunks = c // 128 if c >= 128 else 1
    chunk = min(c, 128)
    for k in range(n_chunks):
        blk = bp[:, k * chunk:(k + 1) * chunk]       # (1, chunk), lane-aligned
        # (TB, w, 1) >= (1, 1, chunk) → (TB, w, chunk)
        ge = (paa[:, :, None] >= blk[0][None, None, :]).astype(jnp.int32)
        acc = acc + ge.sum(-1)
    sax_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("w", "b", "block_b", "interpret"))
def sax_encode(x: jax.Array, *, w: int, b: int, block_b: int = 256,
               interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """``x [B, n] -> (paa [B, w] f32, sax [B, w] i32)``.

    Pads the batch to a multiple of ``block_b``; the breakpoint table is
    padded to a multiple of 128 with ``+inf`` (padding breakpoints never
    count, so symbols are unchanged).
    """
    B, n = x.shape
    if n % w:
        raise ValueError(f"n={n} must be divisible by w={w}")
    c = 1 << b
    Bp = -(-B // block_b) * block_b
    xp = jnp.pad(x.astype(jnp.float32), ((0, Bp - B), (0, 0)))

    seg = jnp.zeros((n, w), jnp.float32)
    idx = jnp.arange(n) // (n // w)
    seg = seg.at[jnp.arange(n), idx].set(w / n)

    bp = jnp.asarray(breakpoints(b), jnp.float32)            # (c-1,)
    c_pad = max(128, -(-(c - 1) // 128) * 128)
    bp = jnp.pad(bp, (0, c_pad - (c - 1)), constant_values=jnp.inf)[None, :]

    grid = (Bp // block_b,)
    paa, sax = pl.pallas_call(
        functools.partial(_kernel, w=w, c=c_pad),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
            pl.BlockSpec((n, w), lambda i: (0, 0)),
            pl.BlockSpec((1, c_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, w), lambda i: (i, 0)),
            pl.BlockSpec((block_b, w), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, w), jnp.float32),
            jax.ShapeDtypeStruct((Bp, w), jnp.int32),
        ],
        interpret=interpret,
    )(xp, seg, bp)
    return paa[:B], sax[:B]
