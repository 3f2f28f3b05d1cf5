"""Dispatching wrappers: Pallas kernel on TPU, pure-jnp oracle elsewhere.

The search path calls these; on a TPU they run the Pallas kernels, on
the CPU the oracles (fast under XLA:CPU), while tests force
``impl='pallas'`` with interpret=True to validate the kernels
themselves. The backend probe is resolved once per process
(``_backend``) instead of re-querying ``jax.default_backend()`` on every
hot-path dispatch; tests still override the choice explicitly via
``impl=``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import hamming as hamming_k
from repro.kernels import l2dist as l2_k
from repro.kernels import page_gather as pg_k
from repro.kernels import page_scan as ps_k
from repro.kernels import pq_adc as adc_k
from repro.kernels import ref


@functools.cache
def _backend() -> str:
    return jax.default_backend()


def _on_tpu() -> bool:
    return _backend() == "tpu"


def _resolve(impl: str | None) -> str:
    return impl or ("pallas" if _on_tpu() else "ref")


def l2_distance(q, x, *, impl: str | None = None, interpret: bool = False):
    if _resolve(impl) == "pallas":
        return l2_k.l2_distance(q, x, interpret=interpret or not _on_tpu())
    return ref.l2_distance_ref(q, x)


def pq_adc(codes, lut, *, impl: str | None = None, interpret: bool = False):
    if _resolve(impl) == "pallas":
        return adc_k.pq_adc(codes, lut, interpret=interpret or not _on_tpu())
    return ref.pq_adc_ref(codes, lut)


def hamming(codes, qcode, *, impl: str | None = None, interpret: bool = False):
    if _resolve(impl) == "pallas":
        return hamming_k.hamming(
            codes, qcode, interpret=interpret or not _on_tpu()
        )
    return ref.hamming_ref(codes, qcode)


def page_gather_l2(pages, page_ids, q, *, impl: str | None = None,
                   interpret: bool = False):
    if _resolve(impl) == "pallas":
        return pg_k.page_gather_l2(
            pages, page_ids, q, interpret=interpret or not _on_tpu()
        )
    return ref.page_gather_l2_ref(pages, page_ids, q)


def delta_scan(q, vecs, live, k: int, *, mask=None, impl: str | None = None,
               interpret: bool = False):
    """Brute-force scan of the mutable index's in-memory delta tier.

    q: (Q, d) f32 queries, vecs: (C, d) f32 delta buffer (C a power of
    two), live: (C,) bool row-validity mask. Routes the distance matrix
    through the batched L2 kernel path (``l2dist`` on TPU, jnp oracle
    elsewhere), masks dead/padded rows to INF, and selects the per-query
    ascending top-k with ``lax.top_k``. ``mask`` (C,) bool is the
    filtered-search predicate over delta rows — rows failing it score INF
    exactly like dead rows (None leaves the program unchanged). The k
    selected rows are re-scored as sums of squared differences, so the
    returned distances are exact (a stored vector is 0.0 from itself) on
    every backend, and re-ranked by them. Returns (dists (Q, k) f32,
    slots (Q, k) int32 row indices into ``vecs``); non-finite entries mean
    fewer than k live rows.
    """
    d = l2_distance(q, vecs, impl=impl, interpret=interpret)
    keep = live if mask is None else live & mask
    d = jnp.where(keep[None, :], d, jnp.inf)
    neg, slots = jax.lax.top_k(-d, k)
    diff = vecs[slots].astype(jnp.float32) - q.astype(jnp.float32)[:, None, :]
    exact = jnp.where(jnp.isfinite(neg), (diff * diff).sum(-1), jnp.inf)
    neg, order = jax.lax.top_k(-exact, k)
    return -neg, jnp.take_along_axis(slots, order, axis=1).astype(jnp.int32)


def page_scan(recs, page_ids, q, lut, *, capacity: int, dim: int, rp: int,
              compute_adc: bool = True, member_mask=None,
              impl: str | None = None, interpret: bool = False):
    """Fused per-page scan: one record DMA -> (member L2, neighbor ADC).

    ``member_mask`` (b, capacity) f32 pushes a filter predicate into the
    scan — members with mask <= 0 score +inf (None: unmasked program,
    unchanged)."""
    if _resolve(impl) == "pallas":
        return ps_k.page_scan(
            recs, page_ids, q, lut,
            capacity=capacity, dim=dim, rp=rp, compute_adc=compute_adc,
            member_mask=member_mask,
            interpret=interpret or not _on_tpu(),
        )
    return ref.page_scan_ref(
        recs, page_ids, q, lut,
        capacity=capacity, dim=dim, rp=rp, compute_adc=compute_adc,
        member_mask=member_mask,
    )


def page_scan_recs(recs_b, q, lut, *, capacity: int, dim: int, rp: int,
                   compute_adc: bool = True, member_mask=None,
                   impl: str | None = None, interpret: bool = False):
    """Fused scan on an already-staged (b, rows, 128) record batch — the
    streaming tier's scoring half (resident gathers + host-fetched misses
    merged upstream). Scores match ``page_scan`` bit for bit; the same
    ``member_mask`` applies (the mask is per page, not per origin)."""
    if _resolve(impl) == "pallas":
        return ps_k.page_scan_recs(
            recs_b, q, lut,
            capacity=capacity, dim=dim, rp=rp, compute_adc=compute_adc,
            member_mask=member_mask,
            interpret=interpret or not _on_tpu(),
        )
    return ref.page_scan_recs_ref(
        recs_b, q, lut,
        capacity=capacity, dim=dim, rp=rp, compute_adc=compute_adc,
        member_mask=member_mask,
    )
