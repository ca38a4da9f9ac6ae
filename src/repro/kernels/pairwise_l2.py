"""Pallas TPU kernel: blocked squared-L2 distance matrix (candidate
verification — the paper's "search the series inside the node" hot spot).

``d2[i,j] = |q_i|^2 + |x_j|^2 - 2 q_i·x_j`` computed as a tiled matmul on the
MXU with the norm terms fused into the final accumulation step.

Grid ``(Q/TQ, X/TX, n/TK)`` — the K dimension is innermost so each (TQ, TX)
output tile is revisited across the contraction and stays resident in VMEM
(standard Pallas matmul schedule; accumulation happens in the output block).
Tiles default to 128×128×512: ``128·512·4B·2`` operands + ``128·128·4B``
accumulator ≈ 0.6 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, x_ref, qn_ref, xn_ref, o_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # HIGHEST: a default-precision f32 MXU pass rounds operands to bf16
    o_ref[...] += jnp.dot(q_ref[...], x_ref[...].T,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _fin():
        qn = qn_ref[...]          # (TQ, 1)
        xn = xn_ref[...]          # (1, TX)
        o_ref[...] = jnp.maximum(qn + xn - 2.0 * o_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("tq", "tx", "tk", "interpret"))
def pairwise_l2(q: jax.Array, x: jax.Array, *, tq: int = 128, tx: int = 128,
                tk: int = 512, interpret: bool = False) -> jax.Array:
    """``q [Q, n]``, ``x [X, n]`` → squared distances ``[Q, X] f32``.

    Inputs are zero-padded to tile multiples (zero padding adds nothing to
    norms or dot products, so results are exact); output is sliced back.
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    Q, n = q.shape
    X = x.shape[0]
    tq = min(tq, max(8, -(-Q // 8) * 8))
    tx = min(tx, max(128, -(-X // 128) * 128))
    Qp, Xp = -(-Q // tq) * tq, -(-X // tx) * tx
    tk = min(tk, max(128, -(-n // 128) * 128))
    Kp = -(-n // tk) * tk
    qp = jnp.pad(q, ((0, Qp - Q), (0, Kp - n)))
    xp = jnp.pad(x, ((0, Xp - X), (0, Kp - n)))
    qn = (qp * qp).sum(-1, keepdims=True)                    # (Qp, 1)
    xn = (xp * xp).sum(-1, keepdims=True).T                  # (1, Xp)

    k_steps = Kp // tk
    grid = (Qp // tq, Xp // tx, k_steps)
    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tx, tk), lambda i, j, k: (j, k)),
            pl.BlockSpec((tq, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, tx), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((tq, tx), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Xp), jnp.float32),
        interpret=interpret,
    )(qp, xp, qn, xn)
    return out[:Q, :X]
