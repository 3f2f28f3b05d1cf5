"""Lightweight LSH routing index (paper Sec 4.3, "Caching for fast
lightweight indexing").

A sample of vectors is projected onto random hyperplanes; the sign pattern is
packed into uint32 words. A query computes its own code, XOR+popcounts against
the sampled codes, and the top-T smallest Hamming distances become the entry
candidates for the page-node graph traversal (Alg. 2, line 4).

Adaptation noted in DESIGN.md: the paper probes all buckets within Hamming
radius r; we take top-T by Hamming distance — identical candidates for small
r, but fixed-shape and TPU-friendly (one XOR/popcount sweep, one top-k).
The sweep's Pallas kernel lives in ``repro.kernels.hamming``.

The sampled vectors' PQ codes are kept alongside (a few KB) so entry
candidates always have an estimated distance, even in DISK_ONLY mode —
this is the paper's 0.05 GB minimum-memory configuration.

Hyperplanes through the origin split data centred near it. Data far from
the origin relative to its spread (uint8 embeddings, every coordinate in
0..255) falls on one side of most of them: on a 192-d uint8 corpus of 64
clusters, 13 of 64 bits split a 1,024-vector sample with a minority of
10% or more, and the sample holds 294 distinct codes. When fewer than
half the bits split the sample so (``COLLAPSED_BITS``), the build passes
the hyperplanes through the sample's mean instead (``LSHIndex.center``;
64 of 64 bits and 964 distinct codes there). Data the origin already
splits keeps ``center=None`` and the codes it always had.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def _popcount32(v: jnp.ndarray) -> jnp.ndarray:
    """Bit-twiddling popcount on uint32 lanes (no intrinsics needed)."""
    v = v.astype(jnp.uint32)
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., B) {0,1} -> (..., B//32) uint32, little-endian within a word."""
    *lead, b = bits.shape
    w = b // 32
    bits = bits.reshape(*lead, w, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (bits << shifts).sum(-1).astype(jnp.uint32)


def hash_codes(x: jnp.ndarray, planes: jnp.ndarray) -> jnp.ndarray:
    """Random-hyperplane binary hash, packed. x: (N, d), planes: (d, B)."""
    bits = (x @ planes > 0).astype(jnp.uint32)
    return pack_bits(bits)


def hamming_distance(codes: jnp.ndarray, qcode: jnp.ndarray) -> jnp.ndarray:
    """Hamming distances between packed codes (S, W) and a query code (W,).

    Pure-jnp oracle for ``repro.kernels.hamming``.
    """
    return _popcount32(jnp.bitwise_xor(codes, qcode[None, :])).sum(-1)


# a bit splits the sample when each side holds at least this share of it
SPLIT_SHARE = 0.1
# the codes collapse when fewer than this share of bits split the sample
COLLAPSED_BITS = 0.5


@dataclasses.dataclass
class LSHIndex:
    planes: jnp.ndarray        # (d, B) float32
    sample_ids: jnp.ndarray    # (S,) int32 — vector ids (reassigned space)
    sample_codes: jnp.ndarray  # (S, B//32) uint32
    sample_pq: jnp.ndarray     # (S, M) uint8 — PQ codes of the sample
    # (d,) float32 point the hyperplanes pass through (the sample mean),
    # or None for the origin (see the module docstring)
    center: jnp.ndarray | None = None

    @property
    def memory_bytes(self) -> int:
        return int(
            self.planes.size * 4
            + self.sample_ids.size * 4
            + self.sample_codes.size * 4
            + self.sample_pq.size
            + (0 if self.center is None else self.center.size * 4)
        )

    def query(self, q: jnp.ndarray, top_t: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Entry vector ids + Hamming distances for a single query (d,)."""
        if self.center is not None:
            q = q - self.center
        qcode = hash_codes(q[None, :], self.planes)[0]
        ham = hamming_distance(self.sample_codes, qcode)
        top = jnp.argsort(ham)[:top_t]
        return self.sample_ids[top], ham[top]


def build_lsh(
    x: np.ndarray,
    pq_codes: np.ndarray,
    bits: int,
    sample: int,
    seed: int = 0,
) -> LSHIndex:
    """Sample vectors, hash them, remember their ids and PQ codes.

    ``x`` must already be in the *reassigned* id space (row i == vector id i)
    so that routed entries can be mapped to pages with id // capacity.
    """
    n, d = x.shape
    rng = np.random.default_rng(seed)
    sample = min(sample, n)
    ids = rng.choice(n, size=sample, replace=False).astype(np.int32)
    planes = rng.standard_normal((d, bits)).astype(np.float32)
    xs = np.asarray(x[ids], np.float32)
    center = None
    if split_bits(xs, planes) < COLLAPSED_BITS * bits:
        center = xs.mean(axis=0)
        xs = xs - center
    codes = hash_codes(jnp.asarray(xs), jnp.asarray(planes))
    return LSHIndex(
        planes=jnp.asarray(planes),
        sample_ids=jnp.asarray(ids),
        sample_codes=codes,
        sample_pq=jnp.asarray(pq_codes[ids]),
        center=None if center is None else jnp.asarray(center),
    )


def split_bits(xs: np.ndarray, planes: np.ndarray) -> int:
    """How many hyperplanes (through the origin) split the rows ``xs``
    with at least ``SPLIT_SHARE`` of them on each side."""
    share = ((xs @ planes) > 0).mean(axis=0)
    return int((np.minimum(share, 1.0 - share) >= SPLIT_SHARE).sum())
