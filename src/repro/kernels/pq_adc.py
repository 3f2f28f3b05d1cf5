"""Pallas TPU kernel: PQ asymmetric distance computation (ADC).

TPU adaptation (DESIGN.md §2): the CPU-idiomatic per-code LUT *gather* is
replaced by a one-hot select — per subspace j, the codes column (bn, 1)
is compared against a lane iota over the K centroids, the hits pick
``lut[j]`` out of a (bn, K) tile, and a lane sum folds it to (bn, 1). The
M subspaces accumulate into one column: gather-free, every value on the
(8, 128) tile, and exact f32 (a select, not a multiply).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _adc_kernel(codes_ref, lut_ref, o_ref):
    codes = codes_ref[...]                        # (bn, M) int32
    lut = lut_ref[...]                            # (M, K) f32
    m, k = lut.shape
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], k), 1)
    acc = jnp.zeros((codes.shape[0], 1), jnp.float32)
    for j in range(m):
        hit = codes[:, j : j + 1] == iota_k       # (bn, K)
        acc = acc + jnp.sum(
            jnp.where(hit, lut[j : j + 1, :], 0.0), axis=-1, keepdims=True
        )
    o_ref[...] = acc                              # (bn, 1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_adc(
    codes: jnp.ndarray,
    lut: jnp.ndarray,
    *,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """codes: (N, M) uint8, lut: (M, K) f32 -> (N,) f32 ADC distances."""
    n0, m = codes.shape
    n = -(-n0 // block_n) * block_n
    cp = jnp.pad(codes.astype(jnp.int32), ((0, n - n0), (0, 0)))
    out = pl.pallas_call(
        _adc_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, m), lambda i: (i, 0)),
            pl.BlockSpec(lut.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(cp, lut.astype(jnp.float32))
    return out[:n0, 0]
