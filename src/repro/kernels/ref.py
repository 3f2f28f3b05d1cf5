"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth; tests sweep shapes/dtypes and
assert_allclose the kernel (interpret=True on CPU) against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import record_layout


def l2_distance_ref(q: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Squared L2 distances. q: (Bq, d), x: (Nx, d) -> (Bq, Nx) f32.

    Expanded form |q|^2 - 2q.x + |x|^2, the oracle of the ``l2dist``
    kernel; the cross term runs at HIGHEST precision. The form need not
    round to 0 for q == x: callers that need an exact self-match
    (``ops.delta_scan``) re-score their top-k by differences.
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    return (
        (q * q).sum(-1)[:, None]
        - 2.0 * jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
        + (x * x).sum(-1)[None, :]
    )


def pq_adc_ref(codes: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """ADC distance. codes: (N, M) uint8, lut: (M, K) f32 -> (N,) f32."""
    idx = codes.astype(jnp.int32)                     # (N, M)
    m = lut.shape[0]
    rows = jnp.arange(m)[None, :]                     # (1, M)
    return lut[rows, idx].astype(jnp.float32).sum(-1)


def hamming_ref(codes: jnp.ndarray, qcode: jnp.ndarray) -> jnp.ndarray:
    """Hamming distance between packed uint32 codes.

    codes: (S, W) uint32, qcode: (W,) uint32 -> (S,) int32.
    """
    v = jnp.bitwise_xor(codes, qcode[None, :]).astype(jnp.uint32)
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    pc = ((v * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)
    return pc.sum(-1)


def page_gather_l2_ref(
    pages: jnp.ndarray, page_ids: jnp.ndarray, q: jnp.ndarray
) -> jnp.ndarray:
    """Gather page records and score members against the query.

    pages: (P, cap, d) f32, page_ids: (b,) int32 (>=0), q: (d,)
    -> (b, cap) squared L2 distances.
    """
    gathered = pages[page_ids]                         # (b, cap, d)
    diff = gathered.astype(jnp.float32) - q.astype(jnp.float32)[None, None, :]
    return (diff * diff).sum(-1)


def page_scan_recs_ref(
    recs_b: jnp.ndarray,
    q: jnp.ndarray,
    lut: jnp.ndarray,
    *,
    capacity: int,
    dim: int,
    rp: int,
    compute_adc: bool = True,
    member_mask: jnp.ndarray | None = None,
):
    """``page_scan_ref`` on records that are ALREADY gathered/staged.

    recs_b: (b, rows, 128) f32 packed page records — the hop's batch as a
    dense array rather than (full store, ids). This is the scoring half of
    the fused scan, split out so the streaming page tier (resident subset
    on device + host-fetched misses) can score a mixed-origin batch.
    ``page_scan_ref`` routes through here, so the two are bit-identical by
    construction — the streaming path's guarantee.

    ``member_mask`` (b, capacity) f32: members with mask <= 0 score
    ``+inf`` — filtered search pushes its predicate into the scan here.
    Neighbor ADC is never masked: the graph must stay traversable
    through filtered-out regions.
    -> (member_d (b, capacity) f32, nbr_d (b, rp) f32 or None).
    """
    b = recs_b.shape[0]
    rv = record_layout.member_rows(capacity, dim)
    if dim <= record_layout.PAGE_LANES:
        vpr = record_layout.vectors_per_row(dim)
        block = recs_b[:, :rv, : vpr * dim]            # (b, Rv, vpr*d)
        vecs = block.reshape(b, rv * vpr, dim)[:, :capacity]
    else:
        rpv = record_layout.rows_per_vector(dim)
        block = recs_b[:, :rv, :]                      # (b, cap*rpv, 128)
        vecs = block.reshape(b, capacity, rpv * record_layout.PAGE_LANES)[
            :, :, :dim
        ]
    diff = vecs.astype(jnp.float32) - q.astype(jnp.float32)[None, None, :]
    member_d = (diff * diff).sum(-1)
    if member_mask is not None:
        member_d = jnp.where(member_mask > 0, member_d, jnp.inf)
    if not compute_adc:
        return member_d, None
    m = lut.shape[0]
    # subspace-major code rows: row Rv+j holds code j of every neighbor
    codes = recs_b[:, rv:rv + m, :rp].astype(jnp.int32)
    rows = jnp.arange(m)[None, :, None]                # (1, M, 1)
    nbr_d = lut[rows, codes].astype(jnp.float32).sum(1)  # (b, rp)
    return member_d, nbr_d


def page_scan_ref(
    recs: jnp.ndarray,
    page_ids: jnp.ndarray,
    q: jnp.ndarray,
    lut: jnp.ndarray,
    *,
    capacity: int,
    dim: int,
    rp: int,
    compute_adc: bool = True,
    member_mask: jnp.ndarray | None = None,
):
    """Fused page scan: one packed-record gather, both score sets.

    recs: (P, rows, 128) f32 packed page records (see
    ``core.layout.pack_page_records``), page_ids: (b,) int32 (>=0),
    q: (d,), lut: (M_disk, K) f32, member_mask: optional (b, capacity)
    f32 filter mask (<= 0 scores +inf; see ``page_scan_recs_ref``).
    -> (member_d (b, capacity) f32, nbr_d (b, rp) f32 or None).
    """
    return page_scan_recs_ref(
        recs[page_ids], q, lut,
        capacity=capacity, dim=dim, rp=rp, compute_adc=compute_adc,
        member_mask=member_mask,
    )
