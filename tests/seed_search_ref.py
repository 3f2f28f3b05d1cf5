"""Frozen copy of the PR-1 (seed) search loop, kept as a test reference.

This is the argsort-based hop body the fused/top-k hot path replaced:
separate ``page_gather_l2`` member scoring and neighbor-code gathers, two
full argsort merges per hop, argsort dedup, and the serial per-pick
``fori_loop`` in ``select_batch``. ``test_search.py`` asserts the optimized
loop in ``repro.core.search`` returns identical results (ids, dists, ios,
hops, cache_hits) on every memory mode — the optimization must be a pure
speedup, not a semantic change. Reads the unpacked ``store.vecs`` /
``store.nbr_codes`` views (the packed record is the optimized path's
concern) through the same jnp oracles the seed dispatched to on CPU.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import pq as pq_mod
from repro.core.config import MemoryMode
from repro.core.lsh import hash_codes
from repro.core.search import SearchResult
from repro.kernels import ref

PAD = -1
INF = jnp.inf


class SeedState(NamedTuple):
    """The seed's per-query loop state, with its own (P,) visited-page
    bitmap (the optimized loop's ``BeamState`` keeps the trail instead)."""

    cand_ids: jnp.ndarray
    cand_d: jnp.ndarray
    cand_vis: jnp.ndarray
    page_vis: jnp.ndarray
    res_ids: jnp.ndarray
    res_d: jnp.ndarray
    io: jnp.ndarray
    cache_hits: jnp.ndarray
    hops: jnp.ndarray


class SeedData(NamedTuple):
    vecs: jnp.ndarray
    member_count: jnp.ndarray
    nbr_ids: jnp.ndarray
    nbr_codes: jnp.ndarray
    nbr_count: jnp.ndarray
    mem_codes: jnp.ndarray
    mem_mask: jnp.ndarray
    mem_codebooks: jnp.ndarray
    disk_codebooks: jnp.ndarray
    cached_pages: jnp.ndarray
    lsh_planes: jnp.ndarray
    lsh_ids: jnp.ndarray
    lsh_codes: jnp.ndarray
    lsh_pq: jnp.ndarray


def _mask_dups_keep_first(ids, d):
    order = jnp.argsort(ids)
    s = ids[order]
    dup_sorted = jnp.concatenate([jnp.array([False]), s[1:] == s[:-1]])
    dup = jnp.zeros_like(dup_sorted).at[order].set(dup_sorted)
    return jnp.where(dup & (ids != PAD), INF, d)


def _init_state(q, data, disk_lut, *, beam, k, entries):
    num_pages = data.vecs.shape[0]
    qcode = hash_codes(q[None], data.lsh_planes)[0]
    ham = ref.hamming_ref(data.lsh_codes, qcode)
    top = jnp.argsort(ham)[:entries]
    entry_ids = data.lsh_ids[top].astype(jnp.int32)
    entry_d = ref.pq_adc_ref(data.lsh_pq[top], disk_lut)
    entry_d = _mask_dups_keep_first(entry_ids, entry_d)
    cand_ids = jnp.full((beam,), PAD, jnp.int32).at[:entries].set(entry_ids)
    cand_d = jnp.full((beam,), INF, jnp.float32).at[:entries].set(entry_d)
    return SeedState(
        cand_ids=cand_ids,
        cand_d=cand_d,
        cand_vis=jnp.zeros((beam,), bool),
        page_vis=jnp.zeros((num_pages,), bool),
        res_ids=jnp.full((k,), PAD, jnp.int32),
        res_d=jnp.full((k,), INF, jnp.float32),
        io=jnp.int32(0),
        cache_hits=jnp.int32(0),
        hops=jnp.int32(0),
    )


def _select_batch(state, *, capacity, io_batch):
    cand_ids = state.cand_ids
    batch = jnp.full((io_batch,), PAD, jnp.int32)

    def pick(j, carry):
        cand_vis, page_vis, batch = carry
        cpages = jnp.where(cand_ids >= 0, cand_ids // capacity, 0)
        stale = (cand_ids != PAD) & page_vis[cpages]
        cand_vis2 = cand_vis | stale
        masked = jnp.where(cand_vis2 | (cand_ids == PAD), INF, state.cand_d)
        slot = jnp.argmin(masked)
        ok = jnp.isfinite(masked[slot])
        cand_vis2 = cand_vis2.at[slot].set(True)
        pid = jnp.where(ok, cand_ids[slot] // capacity, PAD)
        page_vis = jnp.where(
            ok, page_vis.at[jnp.maximum(pid, 0)].set(True), page_vis
        )
        batch = batch.at[j].set(pid)
        return cand_vis2, page_vis, batch

    cand_vis, page_vis, batch = jax.lax.fori_loop(
        0, io_batch, pick, (state.cand_vis, state.page_vis, batch)
    )
    return state._replace(cand_vis=cand_vis, page_vis=page_vis), batch


def _score_members(q, data, batch, *, capacity):
    cap = data.vecs.shape[1]
    safe = jnp.maximum(batch, 0)
    fetched = batch >= 0
    ex = ref.page_gather_l2_ref(data.vecs, safe, q)
    slots = jnp.arange(cap)[None, :]
    ex = jnp.where(slots < data.member_count[safe][:, None], ex, INF)
    ex = jnp.where(fetched[:, None], ex, INF)
    member_ids = (batch[:, None] * capacity + slots).astype(jnp.int32)
    if data.cached_pages.shape[0] > 0:
        pos = jnp.searchsorted(data.cached_pages, safe)
        pos = jnp.minimum(pos, data.cached_pages.shape[0] - 1)
        in_cache = data.cached_pages[pos] == safe
    else:
        in_cache = jnp.zeros_like(fetched)
    io_delta = (fetched & ~in_cache).sum().astype(jnp.int32)
    hit_delta = (fetched & in_cache).sum().astype(jnp.int32)
    return member_ids.ravel(), ex.ravel(), io_delta, hit_delta


def _score_neighbors(data, batch, state, disk_lut, mem_lut, *, capacity, mode):
    rp = data.nbr_ids.shape[1]
    safe = jnp.maximum(batch, 0)
    fetched = batch >= 0
    page_nids = data.nbr_ids[safe]
    page_ncodes = data.nbr_codes[safe]
    page_nc = data.nbr_count[safe]
    flat_nids = page_nids.reshape(-1)
    valid_n = (
        (jnp.arange(rp)[None, :] < page_nc[:, None]).reshape(-1)
        & (flat_nids != PAD)
        & fetched.repeat(rp)
    )
    safe_nids = jnp.maximum(flat_nids, 0)
    est_disk = ref.pq_adc_ref(
        page_ncodes.reshape(-1, page_ncodes.shape[-1]), disk_lut
    )
    if mode == MemoryMode.DISK_ONLY.value:
        est = est_disk
    elif mode == MemoryMode.MEM_ALL.value:
        est = ref.pq_adc_ref(data.mem_codes[safe_nids], mem_lut)
    else:
        est_mem = ref.pq_adc_ref(data.mem_codes[safe_nids], mem_lut)
        est = jnp.where(data.mem_mask[safe_nids], est_mem, est_disk)
    est = jnp.where(valid_n, est, INF)
    est = jnp.where(state.page_vis[safe_nids // capacity], INF, est)
    dup_in_cand = (flat_nids[:, None] == state.cand_ids[None, :]).any(1)
    est = jnp.where(dup_in_cand, INF, est)
    est = _mask_dups_keep_first(flat_nids, est)
    return flat_nids, est


def _merge(state, member_ids, member_d, nbr_ids, nbr_d, io_delta, hit_delta):
    k = state.res_ids.shape[0]
    beam = state.cand_ids.shape[0]
    all_rd = jnp.concatenate([state.res_d, member_d])
    all_ri = jnp.concatenate([state.res_ids, member_ids])
    order = jnp.argsort(all_rd)[:k]
    res_d, res_ids = all_rd[order], all_ri[order]
    all_ci = jnp.concatenate([state.cand_ids, nbr_ids])
    all_cd = jnp.concatenate([state.cand_d, nbr_d])
    all_cv = jnp.concatenate([state.cand_vis, jnp.zeros(nbr_ids.shape, bool)])
    order = jnp.argsort(all_cd)[:beam]
    return state._replace(
        cand_ids=all_ci[order],
        cand_d=all_cd[order],
        cand_vis=all_cv[order],
        res_ids=res_ids,
        res_d=res_d,
        io=state.io + io_delta,
        cache_hits=state.cache_hits + hit_delta,
        hops=state.hops + 1,
    )


def _search_one(q, data, *, capacity, beam, io_batch, k, max_hops, entries, mode):
    disk_lut = pq_mod.pq_lut(q, data.disk_codebooks)
    mem_lut = pq_mod.pq_lut(q, data.mem_codebooks)
    state = _init_state(q, data, disk_lut, beam=beam, k=k, entries=entries)

    def cond(state):
        live = (
            (~state.cand_vis)
            & (state.cand_ids != PAD)
            & jnp.isfinite(state.cand_d)
        )
        return live.any() & (state.hops < max_hops)

    def body(state):
        state, batch = _select_batch(state, capacity=capacity, io_batch=io_batch)
        mids, md, io_delta, hit_delta = _score_members(
            q, data, batch, capacity=capacity
        )
        nids, nd = _score_neighbors(
            data, batch, state, disk_lut, mem_lut, capacity=capacity, mode=mode
        )
        return _merge(state, mids, md, nids, nd, io_delta, hit_delta)

    state = jax.lax.while_loop(cond, body, state)
    return state.res_ids, state.res_d, state.io, state.hops, state.cache_hits


@functools.partial(
    jax.jit,
    static_argnames=(
        "capacity", "beam", "io_batch", "k", "max_hops", "entries", "mode"
    ),
)
def _seed_batch_search(queries, data, *, capacity, beam, io_batch, k,
                       max_hops, entries, mode):
    fn = functools.partial(
        _search_one, data=data, capacity=capacity, beam=beam,
        io_batch=io_batch, k=k, max_hops=max_hops, entries=entries, mode=mode,
    )
    ids, dists, ios, hops, hits = jax.vmap(fn)(queries)
    return SearchResult(ids=ids, dists=dists, ios=ios, hops=hops, cache_hits=hits)


def seed_batch_search(queries, index, k: int = 10) -> SearchResult:
    """Run the frozen seed loop against a built ``PageANNIndex``.

    Returns REASSIGNED ids (same space as ``index._raw_search``).
    """
    store, tier, lsh = index.store, index.tier, index.lsh
    data = SeedData(
        vecs=store.vecs,
        member_count=store.member_count,
        nbr_ids=store.nbr_ids,
        nbr_codes=store.nbr_codes,
        nbr_count=store.nbr_count,
        mem_codes=tier.mem_codes,
        mem_mask=tier.mem_mask,
        mem_codebooks=tier.mem_codebooks,
        disk_codebooks=tier.disk_codebooks,
        cached_pages=tier.cached_pages,
        lsh_planes=lsh.planes,
        lsh_ids=lsh.sample_ids,
        lsh_codes=lsh.sample_codes,
        lsh_pq=lsh.sample_pq,
    )
    cfg = index.cfg
    return _seed_batch_search(
        queries, data,
        capacity=store.capacity, beam=cfg.beam_width, io_batch=cfg.io_batch,
        k=k, max_hops=cfg.max_hops, entries=cfg.lsh_entries,
        mode=cfg.memory_mode.value,
    )
