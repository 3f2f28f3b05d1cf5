"""Pallas TPU kernel: batched squared-L2 distance via the MXU.

dist(q, x) = |q|^2 - 2 q.x + |x|^2 — the -2qx term is a (bq, d) x (d, bx)
matmul that lands on the MXU at HIGHEST precision (the TPU's default
runs f32 operands in bf16 passes); the norms are VPU reductions. Tiles are
(block_q, d) and (block_x, d) VMEM blocks; d stays unblocked (ANN dims are
<= 1024, well within VMEM at f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _l2_kernel(q_ref, x_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)        # (bq, d)
    x = x_ref[...].astype(jnp.float32)        # (bx, d)
    qq = jnp.sum(q * q, axis=-1, keepdims=True)          # (bq, 1)
    xx = jnp.sum(x * x, axis=-1, keepdims=True).T        # (1, bx)
    qx = jax.lax.dot_general(
        q, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                     # (bq, bx)
    o_ref[...] = qq - 2.0 * qx + xx


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_x", "interpret")
)
def l2_distance(
    q: jnp.ndarray,
    x: jnp.ndarray,
    *,
    block_q: int = 128,
    block_x: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (Bq, d), x: (Nx, d) -> (Bq, Nx) f32. Pads to block multiples."""
    bq0, d = q.shape
    nx0, _ = x.shape
    bq = -(-bq0 // block_q) * block_q
    nx = -(-nx0 // block_x) * block_x
    qp = jnp.pad(q, ((0, bq - bq0), (0, 0)))
    xp = jnp.pad(x, ((0, nx - nx0), (0, 0)))
    out = pl.pallas_call(
        _l2_kernel,
        grid=(bq // block_q, nx // block_x),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_x, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_x), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bq, nx), jnp.float32),
        interpret=interpret,
    )(qp, xp)
    return out[:bq0, :nx0]
