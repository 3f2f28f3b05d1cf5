"""Pallas TPU kernel: fused page scan — one DMA per page record, both score
sets.

PageANN's contract is that one graph hop costs exactly one aligned unit of
bulk data movement per page. The seed loop honored that on paper but read
each page record twice: ``page_gather_l2`` DMA'd the member vectors, then a
separate jnp gather re-fetched the same pages' neighbor PQ codes. This
kernel restores the invariant literally: the (b,) page-id batch selected by
Alg. 2 is *scalar-prefetched* into SMEM, and grid step i DMAs the whole
packed record of page ``ids[i]`` — member vectors, transposed neighbor
codes, and counts, one (rows, 128)-lane tile built by
``core.layout.pack_page_records`` mirroring the paper's on-page layout —
HBM->VMEM exactly once. From that single resident block it emits

  * exact member L2 distances  (VPU reduction over the member rows), and
  * neighbor ADC distances     (per-subspace one-hot select against the
    query LUT, the gather-free trick from ``pq_adc.py``),

so one page == one DMA == both score sets. Double buffering of the next
record against the current block's compute falls out of Pallas' pipeline
emitter — the TPU analogue of the paper's Linux-AIO I/O-computation overlap.

Record layout (f32 lanes; arithmetic owned by ``kernels.record_layout``,
packed by ``core.layout.pack_page_records`` — ``vpr = 128 // d`` member
vectors per row for d <= 128, ``rpv = ceil(d / 128)`` rows per vector for
d > 128):
  rows [0, Rv)         member vectors, densely packed; Rv = member_rows
  rows [Rv, Rv+M)      neighbor PQ codes, subspace-major: row Rv+j holds
                       code j of neighbors 0..Rp-1 in cols [0, Rp)
  (rows padded to a multiple of 8 so the tile is (8, 128)-aligned)

Neighbor *ids* and the member/neighbor counts are not scored, so they ride
small int side arrays in ``SearchData`` rather than wasting f32 lanes here.

The transposed code block keeps the ADC lane-parallel: subspace j's codes
sit in one lane vector, so each subspace is one (K, 128) one-hot select
against a LUT column and a sublane sum, with no in-kernel transpose or
gather. Selecting (not multiplying) keeps the estimate exact f32 on every
backend.

Every in-kernel value keeps the record's (rows, lanes) tiling; only the
folds Mosaic cannot lower run outside the kernel on its small outputs.
The member scores leave the kernel as a (b, Rv, W) per-row block, W =
``vpr`` segment sums per row for d <= 128 (one per packed vector) or
W = 1 row sum for d > 128; the wrapper flattens the former and folds
``rpv`` rows per vector of the latter. The query arrives pre-tiled to the
member block's (Rv, 128) shape, so the kernel body is the same for every
dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.record_layout import (
    PAGE_LANES as LANES,
    member_rows as _member_rows,
    rows_per_vector as _rpv,
    vectors_per_row as _vpr,
)


def _row_width(dim: int) -> int:
    """Member scores per record row leaving the kernel (W above)."""
    return _vpr(dim) if dim <= LANES else 1


def _member_scores(rec, qt, dim):
    """(Rv, 128) member rows + (Rv, 128) tiled query -> (Rv, W) sums.

    W == 1 reduces each row over its lanes. For W > 1 (d < 64) each
    packed vector's d-lane segment is summed under its own lane mask, so
    the reduction stays a plain lane sum on the (8, 128) tile.
    """
    diff = rec - qt
    sq = diff * diff
    w = _row_width(dim)
    if w == 1:
        return jnp.sum(sq, axis=-1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (sq.shape[0], w), 1)
    out = jnp.zeros((sq.shape[0], w), jnp.float32)
    for s in range(w):
        seg = (lane >= s * dim) & (lane < (s + 1) * dim)
        part = jnp.sum(jnp.where(seg, sq, 0.0), axis=-1, keepdims=True)
        out = jnp.where(col == s, part, out)
    return out


def _neighbor_adc(codes, lut_t):
    """(M, 128) transposed code rows + (K, M) LUT -> (1, 128) estimates."""
    ksub, m = lut_t.shape
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (ksub, LANES), 0)
    acc = jnp.zeros((1, LANES), jnp.float32)
    for j in range(m):
        hit = iota_k == codes[j : j + 1, :].astype(jnp.int32)      # (K, 128)
        acc = acc + jnp.sum(
            jnp.where(hit, lut_t[:, j : j + 1], 0.0), axis=0, keepdims=True
        )
    return acc


def _scan_kernel(*refs, rv, dim, m, adc, masked, prefetch):
    """One grid step: one packed page record -> member sums (+ ADC row).

    Refs, in order: [ids (scalar prefetch)], record, tiled query,
    [LUT^T], [mask], member out, [ADC out] — bracketed ones per flag.
    A masked member (mask <= 0) scores +inf here, so the hop's running
    top-k only ever holds passing candidates; the neighbor ADC is never
    masked (traversal must pass through filtered-out regions).
    """
    refs = list(refs[1:] if prefetch else refs)
    rec_ref, q_ref = refs.pop(0), refs.pop(0)
    lut_ref = refs.pop(0) if adc else None
    mask_ref = refs.pop(0) if masked else None
    md_ref = refs.pop(0)
    md = _member_scores(rec_ref[0, :rv, :], q_ref[...], dim)
    if masked:
        md = jnp.where(mask_ref[0] > 0, md, jnp.float32(jnp.inf))
    md_ref[0] = md
    if adc:
        refs.pop(0)[0] = _neighbor_adc(rec_ref[0, rv : rv + m, :], lut_ref[...])


def _tiled_query(q, capacity, dim):
    """The query laid out over the member block's (Rv, 128) rows."""
    q = q.astype(jnp.float32)
    if dim <= LANES:
        vpr = _vpr(dim)
        row = jnp.zeros((LANES,), jnp.float32).at[: vpr * dim].set(
            jnp.tile(q, vpr)
        )
        return jnp.tile(row[None, :], (_member_rows(capacity, dim), 1))
    rpv = _rpv(dim)
    rows = jnp.zeros((rpv * LANES,), jnp.float32).at[:dim].set(q)
    return jnp.tile(rows.reshape(rpv, LANES), (capacity, 1))


def _mask_rows(member_mask, capacity, dim):
    """(b, capacity) member mask -> the kernel's (b, Rv, W) score layout."""
    mask = member_mask.astype(jnp.float32)
    b = mask.shape[0]
    rv = _member_rows(capacity, dim)
    if dim <= LANES:
        w = _row_width(dim)
        mask = jnp.pad(mask, ((0, 0), (0, rv * w - capacity)))
        return mask.reshape(b, rv, w)
    return jnp.repeat(mask, _rpv(dim), axis=1)[:, :, None]


def _fold_members(md, capacity, dim):
    """(b, Rv, W) kernel scores -> (b, capacity) member distances."""
    b = md.shape[0]
    if dim <= LANES:
        return md.reshape(b, -1)[:, :capacity]
    return md.reshape(b, capacity, _rpv(dim)).sum(-1)


def _scan(recs, page_ids, q, lut, member_mask, *, capacity, dim, rp,
          compute_adc, interpret):
    """Shared body of both entry points. ``page_ids`` None walks ``recs``
    in order (grid step i DMAs record i); otherwise the ids are
    scalar-prefetched and step i DMAs record ``page_ids[i]``."""
    _, rows, lanes = recs.shape
    assert lanes == LANES and rp <= LANES
    prefetch = page_ids is not None
    b = page_ids.shape[0] if prefetch else recs.shape[0]
    m = lut.shape[0]
    rv = _member_rows(capacity, dim)
    w = _row_width(dim)
    masked = member_mask is not None

    def spec(shape, index):
        # index_map gets the scalar-prefetch ref as a trailing argument
        if prefetch:
            return pl.BlockSpec(shape, lambda i, ids: index(i, ids))
        return pl.BlockSpec(shape, lambda i: index(i, None))

    qt = _tiled_query(q, capacity, dim)
    args = [recs, qt]
    in_specs = [
        spec((1, rows, LANES),
             lambda i, ids: (ids[i] if prefetch else i, 0, 0)),
        spec(qt.shape, lambda i, ids: (0, 0)),
    ]
    if compute_adc:
        lut_t = lut.astype(jnp.float32).T                   # (K, M)
        args.append(lut_t)
        in_specs.append(spec(lut_t.shape, lambda i, ids: (0, 0)))
    if masked:
        args.append(_mask_rows(member_mask, capacity, dim))
        in_specs.append(spec((1, rv, w), lambda i, ids: (i, 0, 0)))
    out_specs = [spec((1, rv, w), lambda i, ids: (i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, rv, w), jnp.float32)]
    if compute_adc:
        out_specs.append(spec((1, 1, LANES), lambda i, ids: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, 1, LANES), jnp.float32))
    kernel = functools.partial(
        _scan_kernel, rv=rv, dim=dim, m=m, adc=compute_adc, masked=masked,
        prefetch=prefetch,
    )
    if prefetch:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b,),
            in_specs=in_specs, out_specs=out_specs,
        )
        outs = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret,
        )(page_ids.astype(jnp.int32), *args)
    else:
        outs = pl.pallas_call(
            kernel, grid=(b,), in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, interpret=interpret,
        )(*args)
    md = _fold_members(outs[0], capacity, dim)
    if not compute_adc:
        return md, None
    return md, outs[1][:, 0, :rp]


@functools.partial(
    jax.jit, static_argnames=("capacity", "dim", "rp", "compute_adc", "interpret")
)
def page_scan_recs(
    recs_b: jnp.ndarray,
    q: jnp.ndarray,
    lut: jnp.ndarray,
    *,
    capacity: int,
    dim: int,
    rp: int,
    compute_adc: bool = True,
    interpret: bool = False,
    member_mask: jnp.ndarray | None = None,
):
    """``page_scan`` on an ALREADY-staged record batch: recs_b (b, rows,
    128) f32, q: (d,), lut: (M_disk, K) f32, member_mask: optional
    (b, capacity) f32 filter mask (<= 0 members score +inf in-kernel;
    None traces the unmasked kernel).

    The scoring half of the fused scan for the streaming page tier: the
    hop's records arrive as a dense batch (resident gathers merged with
    host-fetched misses), so the grid walks them in order — no scalar
    prefetch, grid step i DMAs record i. Same per-record compute as the
    fused kernel (``_scan_kernel``), so scores match the id-indexed path
    bit for bit.
    -> (member_d (b, capacity) f32, nbr_d (b, rp) f32 or None)
    """
    return _scan(
        recs_b, None, q, lut, member_mask, capacity=capacity, dim=dim,
        rp=rp, compute_adc=compute_adc, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("capacity", "dim", "rp", "compute_adc", "interpret")
)
def page_scan(
    recs: jnp.ndarray,
    page_ids: jnp.ndarray,
    q: jnp.ndarray,
    lut: jnp.ndarray,
    *,
    capacity: int,
    dim: int,
    rp: int,
    compute_adc: bool = True,
    interpret: bool = False,
    member_mask: jnp.ndarray | None = None,
):
    """recs: (P, rows, 128) packed page records, page_ids: (b,) int32 in
    [0, P), q: (d,), lut: (M_disk, K) f32 query LUT, member_mask:
    optional (b, capacity) f32 filter mask — per BATCH position (already
    gathered for the hop's pages), not per store page; <= 0 members
    score +inf in-kernel. None traces the unmasked kernel.

    -> (member_d (b, capacity) f32, nbr_d (b, rp) f32 or None)

    ``compute_adc=False`` (MEM_ALL mode: neighbor codes live in the memory
    tier) skips the ADC entirely and returns ``nbr_d=None``.
    """
    return _scan(
        recs, page_ids, q, lut, member_mask, capacity=capacity, dim=dim,
        rp=rp, compute_adc=compute_adc, interpret=interpret,
    )
