"""PageANN graph search — Algorithm 2, as a fixed-shape JAX program.

Per query the loop maintains a :class:`BeamState`:
  * a candidate set (size-L, distance-sorted, visited flags) over *vector*
    ids in the reassigned space (page = id // capacity),
  * a visited-page bitmap (the paper's visited set V),
  * a running exact-distance result set (size-K),
and per hop applies three pure transition functions:

  ``select_batch``      pick up to b closest unvisited candidates on fresh
                        pages — the I/O schedule for this hop — as ONE
                        vectorized pass: a single stable sort of the beam
                        by (distance, slot) plus a first-occurrence-per-
                        page mask, no serial argmin loop,
  ``score_page_batch``  read those packed page records in one batched DMA
                        (the I/O unit; ``kernels.ops.page_scan`` — scalar-
                        prefetched page-record DMA on TPU, jnp oracle on
                        CPU) and emit BOTH score sets from the single
                        resident record: exact member L2 distances and
                        neighbor ADC distances (on-page codes from the
                        same record; in-memory codes via
                        ``kernels.ops.pq_adc`` per the coordination mode),
  ``merge``             fold both score sets into the beam and result
                        top-k via ``jax.lax.top_k`` — no full sorts.

The hot loop is argsort-free: merges use ``lax.top_k``, batch-local dedup
is one ``lax.sort`` + segment-boundary mask, and beam-membership tests are
sorted ``searchsorted`` probes instead of O(b*Rp*L) broadcasts.

Everything is fixed-shape: the loop is a ``lax.while_loop``, queries are
vmapped (``batch_search``) and optionally sharded over a device mesh
(``shard_search`` — pad rows carry ``valid=False`` and exit at hop 0).
Runtime knobs (beam L, io batch b, max hops, LSH top-T, k) arrive per call
as a frozen :class:`repro.core.config.SearchParams` used as a static jit
argument — one compiled executable per distinct value, over one index.
I/O and cache-hit counters reproduce the paper's "Mean I/Os" metric.
Later async-prefetch / cache-eviction work should extend the transition
functions, not re-inline the loop.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compat
from repro.core import pq as pq_mod
from repro.core.config import MemoryMode, SearchParams
from repro.core.filter import CompiledFilter, MetaArrays, filter_mask
from repro.core.layout import MemoryTier, PageStore
from repro.core.lsh import LSHIndex, hash_codes
from repro.kernels import ops

PAD = -1
INF = jnp.inf


class SearchData(NamedTuple):
    """All device arrays the search touches (a single pytree argument)."""

    # disk tier: packed page records (members + neighbor codes + counts in
    # one (rows, 128) tile per page — see core.layout.pack_page_records).
    # Under a MemoryBudget, page_recs holds only the RESIDENT subset
    # (R <= P rows) and resident_map routes each logical page id to its
    # resident slot (-1 = streamed from the host memmap per hop); fully
    # resident indexes carry resident_map == arange(P) with R == P.
    page_recs: jnp.ndarray     # (R, rows, 128) f32
    member_count: jnp.ndarray  # (P,)
    nbr_ids: jnp.ndarray       # (P, Rp)
    nbr_count: jnp.ndarray     # (P,)
    resident_map: jnp.ndarray  # (P,) int32: slot into page_recs, or -1
    # memory tier
    mem_codes: jnp.ndarray     # (N_pad, M_mem)
    mem_mask: jnp.ndarray      # (N_pad,)
    mem_codebooks: jnp.ndarray
    disk_codebooks: jnp.ndarray
    cached_pages: jnp.ndarray  # (C,) sorted
    # routing index
    lsh_planes: jnp.ndarray
    lsh_ids: jnp.ndarray
    lsh_codes: jnp.ndarray
    lsh_pq: jnp.ndarray        # (S, M_disk)


def make_search_data(store: PageStore, tier: MemoryTier, lsh: LSHIndex) -> SearchData:
    resident_map = store.resident_map
    if resident_map is None:
        # fully resident: the identity routing (page id == resident slot)
        resident_map = jnp.arange(store.recs.shape[0], dtype=jnp.int32)
    return SearchData(
        page_recs=store.recs,
        member_count=store.member_count,
        nbr_ids=store.nbr_ids,
        nbr_count=store.nbr_count,
        resident_map=resident_map,
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


class SearchResult(NamedTuple):
    ids: jnp.ndarray      # (Q, k) reassigned vector ids
    dists: jnp.ndarray    # (Q, k) exact squared distances
    ios: jnp.ndarray      # (Q,) page reads that went to 'disk'
    hops: jnp.ndarray     # (Q,) while_loop iterations
    cache_hits: jnp.ndarray  # (Q,) page reads served by the warmed cache


class BeamState(NamedTuple):
    """Per-query loop state of Algorithm 2 (one pytree, while_loop carry).

    The two adaptive fields are ``None`` — absent from the pytree — unless
    per-query early termination is on (``AdaptiveParams.patience``), so the
    non-adaptive loop carries the exact pre-adaptive structure and compiles
    to the same program.
    """

    cand_ids: jnp.ndarray   # (L,) candidate vector ids, PAD padded
    cand_d: jnp.ndarray     # (L,) estimated distances, INF padded
    cand_vis: jnp.ndarray   # (L,) expanded/scheduled flags
    page_vis: jnp.ndarray   # (P,) visited-page bitmap (the paper's V)
    res_ids: jnp.ndarray    # (k,) running exact top-k ids
    res_d: jnp.ndarray      # (k,) running exact top-k distances
    io: jnp.ndarray         # () page reads served from 'disk'
    cache_hits: jnp.ndarray  # () page reads served by the warmed cache
    hops: jnp.ndarray       # () loop iterations
    # early termination (None unless patience is set): the worst running
    # top-k distance at the last improving hop, and how many consecutive
    # hops failed to improve it by more than epsilon
    frontier: jnp.ndarray | None = None   # () f32
    stall: jnp.ndarray | None = None      # () int32 patience counter


def _mask_dups_keep_first(ids: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """Set distance to INF for duplicate ids (keeping the first occurrence).

    One stable value sort of (ids, positions) + a segment-boundary compare;
    duplicate flags are scattered back through the carried positions — no
    argsort on the hot path.
    """
    n = ids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    s, spos = jax.lax.sort((ids, pos), num_keys=1, is_stable=True)
    dup_sorted = jnp.concatenate([jnp.array([False]), s[1:] == s[:-1]])
    dup = jnp.zeros((n,), bool).at[spos].set(dup_sorted)
    return jnp.where(dup & (ids != PAD), INF, d)


def _top_k_merge(d: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Ascending top-k of a distance vector: (dists, indices).

    ``lax.top_k`` breaks ties toward lower indices, matching a stable
    ascending argsort — same selection, a fraction of the cost.
    """
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


# --------------------------------------------------------------------------
# per-hop transition functions (pure; composed by _search_one's loop body)
# --------------------------------------------------------------------------

def init_state(
    q: jnp.ndarray,
    data: SearchData,
    disk_lut: jnp.ndarray,
    *,
    beam: int,
    k: int,
    entries: int,
    entry_slack: int | None = None,
    min_entries: int = 1,
    patience: int | None = None,
) -> BeamState:
    """In-memory routing (Alg. 2 line 4, Fig. 6 step 1): LSH entry points.

    With query-sensitive entry selection on (``entry_slack`` is not None),
    the top-T Hamming profile becomes a per-query entry-quality signal:
    only candidates within ``entry_slack`` bits of the best candidate seed
    the beam (at least ``min_entries`` by rank). A confidently-routed query
    — a sharply peaked profile — starts from its few genuinely close
    entries instead of the fixed top-T slice, so it schedules fewer junk
    pages on the opening hops; a flat profile (poorly routed, hard query)
    keeps the whole top-T. Fixed-shape and vmap-safe: dropped candidates
    are masked to PAD/INF in place, never compacted.
    """
    num_pages = data.resident_map.shape[0]
    qcode = hash_codes(q[None], data.lsh_planes)[0]
    ham = ops.hamming(data.lsh_codes, qcode)
    ham_top, top = _top_k_merge(ham.astype(jnp.float32), entries)
    entry_ids = data.lsh_ids[top].astype(jnp.int32)
    entry_d = ops.pq_adc(data.lsh_pq[top], disk_lut)
    if entry_slack is not None:
        keep = (ham_top <= ham_top[0] + float(entry_slack)) | (
            jnp.arange(entries) < min_entries
        )
        entry_ids = jnp.where(keep, entry_ids, PAD)
        entry_d = jnp.where(keep, entry_d, INF)
    entry_d = _mask_dups_keep_first(entry_ids, entry_d)

    cand_ids = jnp.full((beam,), PAD, jnp.int32).at[:entries].set(entry_ids)
    cand_d = jnp.full((beam,), INF, jnp.float32).at[:entries].set(entry_d)
    return BeamState(
        cand_ids=cand_ids,
        cand_d=cand_d,
        cand_vis=jnp.zeros((beam,), bool),
        page_vis=jnp.zeros((num_pages,), bool),
        res_ids=jnp.full((k,), PAD, jnp.int32),
        res_d=jnp.full((k,), INF, jnp.float32),
        io=jnp.int32(0),
        cache_hits=jnp.int32(0),
        hops=jnp.int32(0),
        frontier=None if patience is None else jnp.float32(INF),
        stall=None if patience is None else jnp.int32(0),
    )


def select_batch(
    state: BeamState, *, capacity: int, io_batch: int
) -> tuple[BeamState, jnp.ndarray]:
    """Pick up to b closest unvisited candidates whose pages are fresh.

    One vectorized pass replacing the seed's serial per-pick ``fori_loop``:
    stable-sort the beam by (masked distance, slot), keep the first
    occurrence of each page among finite entries, and take the first b —
    exactly the pages the iterated argmin would have scheduled, in the same
    order. Returns the updated state (selected candidates expanded, their
    pages marked visited, candidates on stale pages retired) and the (b,)
    batch of page ids to read, PAD padded.
    """
    cand_ids = state.cand_ids
    beam = cand_ids.shape[0]
    num_pages = state.page_vis.shape[0]
    b = io_batch

    cpages = jnp.where(cand_ids >= 0, cand_ids // capacity, 0)
    # retire candidates whose page was visited before this hop
    stale = (cand_ids != PAD) & state.page_vis[cpages]
    masked = jnp.where(
        state.cand_vis | stale | (cand_ids == PAD), INF, state.cand_d
    )

    slot = jnp.arange(beam, dtype=jnp.int32)
    sd, sslot = jax.lax.sort((masked, slot), num_keys=1, is_stable=True)
    spages = cpages[sslot]
    finite = jnp.isfinite(sd)
    # first finite occurrence of each page in (distance, slot) order
    earlier_same = (
        (spages[:, None] == spages[None, :])
        & (slot[None, :] < slot[:, None])      # strictly earlier sorted pos
        & finite[None, :]
    ).any(1)
    first = finite & ~earlier_same
    rank = jnp.cumsum(first) - first           # fresh pages scheduled before
    scheduled = first & (rank < b)
    n_sched = scheduled.sum()

    batch = (
        jnp.full((b,), PAD, jnp.int32)
        .at[jnp.where(scheduled, rank, b)]
        .set(spages.astype(jnp.int32), mode="drop")
    )
    page_vis = state.page_vis.at[
        jnp.where(scheduled, spages, num_pages)
    ].set(True, mode="drop")

    # expanded flags: the b scheduled picks, plus co-page candidates of any
    # page scheduled before the final pick (the serial loop's stale marking
    # ran once more after each pick except the last)
    early_pv = (
        jnp.zeros_like(state.page_vis)
        .at[jnp.where(scheduled & (rank < b - 1), spages, num_pages)]
        .set(True, mode="drop")
    )
    cand_vis = state.cand_vis | stale
    cand_vis = cand_vis.at[jnp.where(scheduled, sslot, beam)].set(
        True, mode="drop"
    )
    cand_vis = cand_vis | ((cand_ids != PAD) & early_pv[cpages])
    # the serial argmin marked slot 0 on every exhausted pick (all-INF mask)
    cand_vis = cand_vis.at[0].set(cand_vis[0] | (n_sched < b))
    return state._replace(cand_vis=cand_vis, page_vis=page_vis), batch


def page_member_mask(
    meta: MetaArrays, cfilter: CompiledFilter, batch: jnp.ndarray,
    *, capacity: int,
) -> jnp.ndarray:
    """Evaluate a compiled filter over one hop's page batch.

    ``meta`` holds page-slot-aligned metadata columns ((P*cap, T) tags /
    (P*cap, N) numerics — the same ``new_to_old`` layout the page records
    use), so a page's rows are one contiguous slice: gather the (b,)
    batch and evaluate the predicate to a (b, cap) f32 mask (1 = passes).
    Pad slots carry the missing sentinels (-1 / NaN) and never pass.
    """
    # explicit page count: a zero-width column block (schema with no tag
    # or no numeric fields) cannot infer it from a -1 reshape
    pages = meta.tags.shape[0] // capacity
    tags = meta.tags.reshape(pages, capacity, meta.tags.shape[-1])[batch]
    nums = meta.nums.reshape(pages, capacity, meta.nums.shape[-1])[batch]
    return filter_mask(cfilter, tags, nums).astype(jnp.float32)


def score_page_batch(
    q: jnp.ndarray,
    data: SearchData,
    batch: jnp.ndarray,
    state: BeamState,
    disk_lut: jnp.ndarray,
    mem_lut: jnp.ndarray | None,
    *,
    capacity: int,
    mode: str,
    fetch=None,
    meta: MetaArrays | None = None,
    cfilter: CompiledFilter | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched page-record read (Fig. 6 steps 2-4, THE I/O) -> both score
    sets from one DMA per page.

    ``kernels.ops.page_scan`` scalar-prefetches the (b,) page ids and, per
    grid step, DMAs ONE packed record (members + neighbor codes + counts)
    HBM->VMEM, emitting exact member L2 distances and on-page neighbor ADC
    distances from the same resident block. MEM_ALL skips the on-page ADC
    (``compute_adc=False``) and HYBRID/MEM_ALL re-score covered neighbors
    with the finer in-memory codes via ``kernels.ops.pq_adc``.

    ``fetch`` is the streaming page tier's host hook (see
    ``stream_search``): when set, ``data.page_recs`` holds only the
    resident subset. Resident lanes are scored by the SAME fused
    ``ops.page_scan`` gather+scan over the device store the fully
    resident graph uses (identical op pattern -> identical codegen ->
    bit-identical floats); misses are pulled from the host memmap by the
    callback and scored from that staged buffer by
    ``ops.page_scan_recs`` (same per-record arithmetic). The two score
    sets merge per lane — record bytes are exact copies either way, so
    every score matches the fully resident search bit for bit.
    ``fetch=None`` (fully resident) keeps the one-array fused scan
    untouched.

    With a filter bound (``meta`` + ``cfilter``), the predicate is
    evaluated over the batch's page-slot-aligned metadata and pushed into
    the scan as a member mask: filtered-out members score ``+inf`` INSIDE
    the kernel, so the running result top-k only ever holds passing
    candidates. Neighbor ADC estimates stay unmasked — the graph must
    remain traversable through filtered-out regions to reach passing
    ones. With no filter both are ``None`` and the traced program is the
    exact pre-filter one.

    Returns (member_ids, member_dists) flattened to (b*cap,),
    (neighbor_ids, estimated_dists) flattened to (b*Rp,) and INF-masked,
    plus this hop's disk-I/O and cache-hit deltas.
    """
    cap = capacity
    rp = data.nbr_ids.shape[1]
    safe = jnp.maximum(batch, 0)
    fetched = batch >= 0

    member_mask = (
        page_member_mask(meta, cfilter, safe, capacity=cap)
        if meta is not None and cfilter is not None
        else None
    )
    compute_adc = mode != MemoryMode.MEM_ALL.value
    if fetch is None:
        ex, est_disk = ops.page_scan(
            data.page_recs, safe, q, disk_lut,
            capacity=cap, dim=q.shape[0], rp=rp, compute_adc=compute_adc,
            member_mask=member_mask,
        )
    else:
        slot = data.resident_map[safe]                  # (b,)
        resident = slot >= 0
        # host fetch only what the device lacks; everything else (resident
        # pages, unselected PAD lanes) is masked to -1 and comes back as a
        # zero record whose scores are discarded by the per-lane merge /
        # downstream validity masks
        staged = fetch(jnp.where(fetched & ~resident, safe, PAD))
        # the mask is a function of the page id alone, so the SAME (b,
        # cap) mask applies to the resident and staged lanes of the hop
        ex_r, est_r = ops.page_scan(
            data.page_recs, jnp.where(resident, slot, 0), q, disk_lut,
            capacity=cap, dim=q.shape[0], rp=rp, compute_adc=compute_adc,
            member_mask=member_mask,
        )
        ex_s, est_s = ops.page_scan_recs(
            staged, q, disk_lut,
            capacity=cap, dim=q.shape[0], rp=rp, compute_adc=compute_adc,
            member_mask=member_mask,
        )
        ex = jnp.where(resident[:, None], ex_r, ex_s)
        est_disk = (
            None if est_r is None
            else jnp.where(resident[:, None], est_r, est_s)
        )
    slots = jnp.arange(cap)[None, :]
    ex = jnp.where(slots < data.member_count[safe][:, None], ex, INF)
    ex = jnp.where(fetched[:, None], ex, INF)
    member_ids = (batch[:, None] * capacity + slots).astype(jnp.int32)

    # warmed page cache (Sec 4.3): sorted-membership test
    if data.cached_pages.shape[0] > 0:
        pos = jnp.searchsorted(data.cached_pages, safe)
        pos = jnp.minimum(pos, data.cached_pages.shape[0] - 1)
        in_cache = data.cached_pages[pos] == safe
    else:
        in_cache = jnp.zeros_like(fetched)
    io_delta = (fetched & ~in_cache).sum().astype(jnp.int32)
    hit_delta = (fetched & in_cache).sum().astype(jnp.int32)

    # neighbor estimates (Fig. 6 steps 3-4) per the coordination mode
    page_nids = data.nbr_ids[safe]                          # (b, Rp)
    flat_nids = page_nids.reshape(-1)                       # (b*Rp,)
    valid_n = (
        (jnp.arange(rp)[None, :] < data.nbr_count[safe][:, None]).reshape(-1)
        & (flat_nids != PAD)
        & fetched.repeat(rp)
    )
    safe_nids = jnp.maximum(flat_nids, 0)
    if mode == MemoryMode.DISK_ONLY.value:
        est = est_disk.reshape(-1)
    elif mode == MemoryMode.MEM_ALL.value:
        est = ops.pq_adc(data.mem_codes[safe_nids], mem_lut)
    else:  # HYBRID: prefer the higher-accuracy in-memory codes
        est_mem = ops.pq_adc(data.mem_codes[safe_nids], mem_lut)
        est = jnp.where(data.mem_mask[safe_nids], est_mem, est_disk.reshape(-1))
    est = jnp.where(valid_n, est, INF)
    # skip neighbors on already-visited pages
    est = jnp.where(state.page_vis[safe_nids // capacity], INF, est)
    # skip neighbors already in the candidate set: sorted membership probe
    sorted_cand = jnp.sort(state.cand_ids)
    pos = jnp.searchsorted(sorted_cand, flat_nids)
    pos = jnp.minimum(pos, sorted_cand.shape[0] - 1)
    est = jnp.where(sorted_cand[pos] == flat_nids, INF, est)
    # dedupe within this batch
    est = _mask_dups_keep_first(flat_nids, est)
    return member_ids.ravel(), ex.ravel(), flat_nids, est, io_delta, hit_delta


def merge(
    state: BeamState,
    member_ids: jnp.ndarray,
    member_d: jnp.ndarray,
    nbr_ids: jnp.ndarray,
    nbr_d: jnp.ndarray,
    io_delta: jnp.ndarray,
    hit_delta: jnp.ndarray,
    *,
    patience: int | None = None,
    epsilon: float = 0.0,
) -> BeamState:
    """Fold exact member scores into the result top-k and estimated
    neighbor scores into the beam (Alg. 2 line 12, Fig. 6 step 5) —
    ``lax.top_k`` selections, no full argsort merges.

    With early termination on (``patience``), this is also where the
    convergence signal updates: the worst of the new top-k either improved
    on the carried frontier by more than ``epsilon`` (stall resets) or it
    did not (stall increments) — the loop cond trips the lane once stall
    reaches ``patience``."""
    k = state.res_ids.shape[0]
    beam = state.cand_ids.shape[0]

    all_rd = jnp.concatenate([state.res_d, member_d])
    all_ri = jnp.concatenate([state.res_ids, member_ids])
    res_d, order = _top_k_merge(all_rd, k)
    res_ids = all_ri[order]

    all_ci = jnp.concatenate([state.cand_ids, nbr_ids])
    all_cd = jnp.concatenate([state.cand_d, nbr_d])
    all_cv = jnp.concatenate(
        [state.cand_vis, jnp.zeros(nbr_ids.shape, bool)]
    )
    cand_d, order = _top_k_merge(all_cd, beam)
    if patience is None:
        frontier, stall = state.frontier, state.stall
    else:
        # the running top-k only tightens, so the worst slot is monotone
        # non-increasing; "improved" means it dropped by more than epsilon
        # since the previous hop (INF - finite epsilon stays INF, so the
        # unfilled opening hops compare correctly)
        worst = res_d[k - 1]
        improved = worst < state.frontier - jnp.float32(epsilon)
        frontier = worst
        stall = jnp.where(improved, jnp.int32(0), state.stall + 1)
    return state._replace(
        cand_ids=all_ci[order],
        cand_d=cand_d,
        cand_vis=all_cv[order],
        res_ids=res_ids,
        res_d=res_d,
        io=state.io + io_delta,
        cache_hits=state.cache_hits + hit_delta,
        hops=state.hops + 1,
        frontier=frontier,
        stall=stall,
    )


def _search_one(
    q: jnp.ndarray,
    valid: jnp.ndarray,
    data: SearchData,
    *,
    capacity: int,
    beam: int,
    io_batch: int,
    k: int,
    max_hops: int,
    entries: int,
    mode: str,
    fetch=None,
    patience: int | None = None,
    epsilon: float = 0.0,
    entry_slack: int | None = None,
    min_entries: int = 1,
    meta: MetaArrays | None = None,
    cfilter: CompiledFilter | None = None,
):
    disk_lut = pq_mod.pq_lut(q, data.disk_codebooks)  # (M_disk, ksub)
    # the finer in-memory LUT is dead weight in DISK_ONLY mode — skip it
    mem_lut = (
        pq_mod.pq_lut(q, data.mem_codebooks)          # (M_mem, ksub)
        if mode != MemoryMode.DISK_ONLY.value
        else None
    )
    state = init_state(
        q, data, disk_lut, beam=beam, k=k, entries=entries,
        entry_slack=entry_slack, min_entries=min_entries, patience=patience,
    )

    def cond(state: BeamState):
        live = (
            (~state.cand_vis)
            & (state.cand_ids != PAD)
            & jnp.isfinite(state.cand_d)
        )
        go = live.any() & (state.hops < max_hops) & valid
        if patience is not None:
            # per-query early termination: once the worst of the top-k
            # stalled for `patience` consecutive hops, this lane exits
            # (vmap freezes it via select while stragglers keep hopping)
            go = go & (state.stall < patience)
        return go

    def body(state: BeamState):
        state, batch = select_batch(
            state, capacity=capacity, io_batch=io_batch
        )
        mids, md, nids, nd, io_delta, hit_delta = score_page_batch(
            q, data, batch, state, disk_lut, mem_lut,
            capacity=capacity, mode=mode, fetch=fetch,
            meta=meta, cfilter=cfilter,
        )
        return merge(
            state, mids, md, nids, nd, io_delta, hit_delta,
            patience=patience, epsilon=epsilon,
        )

    state = jax.lax.while_loop(cond, body, state)
    return state.res_ids, state.res_d, state.io, state.hops, state.cache_hits


def _batch_search_impl(
    queries: jnp.ndarray,
    data: SearchData,
    valid: jnp.ndarray,
    *,
    capacity: int,
    beam: int,
    io_batch: int,
    k: int,
    max_hops: int,
    entries: int,
    mode: str,
    fetch=None,
    patience: int | None = None,
    epsilon: float = 0.0,
    entry_slack: int | None = None,
    min_entries: int = 1,
    meta: MetaArrays | None = None,
    cfilter: CompiledFilter | None = None,
) -> SearchResult:
    fn = functools.partial(
        _search_one,
        data=data,
        capacity=capacity,
        beam=beam,
        io_batch=io_batch,
        k=k,
        max_hops=max_hops,
        entries=entries,
        mode=mode,
        fetch=fetch,
        patience=patience,
        epsilon=epsilon,
        entry_slack=entry_slack,
        min_entries=min_entries,
        meta=meta,
        cfilter=cfilter,
    )
    ids, dists, ios, hops, hits = jax.vmap(fn)(queries, valid)
    return SearchResult(ids=ids, dists=dists, ios=ios, hops=hops, cache_hits=hits)


def _impl_kwargs(params: SearchParams, capacity: int, mode: str) -> dict:
    problems = params.pageann_violations()
    if problems:
        # every violated invariant in ONE error, not first-wins
        raise ValueError(
            "invalid SearchParams for PageANN search: " + "; ".join(problems)
        )
    a = params.adaptive
    return dict(
        capacity=capacity,
        beam=params.beam_width,
        io_batch=params.io_batch,
        k=params.k,
        max_hops=params.max_hops,
        entries=params.lsh_entries,
        mode=mode,
        patience=None if a is None else a.patience,
        epsilon=0.0 if a is None else a.epsilon,
        entry_slack=None if a is None else a.entry_slack_bits,
        min_entries=1 if a is None else a.min_entries,
    )


@functools.partial(
    jax.jit, static_argnames=("params", "capacity", "mode", "cfilter")
)
def batch_search(
    queries: jnp.ndarray,
    data: SearchData,
    params: SearchParams,
    *,
    capacity: int,
    mode: str,
    meta: MetaArrays | None = None,
    cfilter: CompiledFilter | None = None,
) -> SearchResult:
    """Search a batch of queries. queries: (Q, d).

    ``params`` carries the per-call runtime knobs (beam L, io batch b,
    max hops, LSH top-T, k) and, being frozen/hashable, is a *static* jit
    argument: each distinct ``SearchParams`` value keys one compiled
    executable over the same built index. ``capacity`` and ``mode`` are
    build-time properties of the index artifact.

    Filtered search binds ``meta`` (page-slot-aligned metadata columns, a
    dynamic pytree) and ``cfilter`` (the compiled predicate — frozen
    tuples, another static arg, so each distinct predicate keys its own
    executable). Both default to ``None``, and because ``meta`` is an
    argument rather than a ``SearchData`` field, the no-filter call keeps
    the exact pre-filter jit signature and traces the identical program.
    """
    valid = jnp.ones((queries.shape[0],), bool)
    return _batch_search_impl(
        queries, data, valid, meta=meta, cfilter=cfilter,
        **_impl_kwargs(params, capacity, mode),
    )


# --------------------------------------------------------------------------
# streaming entry point: resident subset on device, misses fetched per hop
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _stream_search_fn(
    fetcher, params: SearchParams, capacity: int, mode: str,
    cfilter: CompiledFilter | None = None,
):
    """jitted streaming search bound to one host fetcher.

    Cached per (fetcher, params, capacity, mode, cfilter): the fetcher is
    baked into the executable as the hop body's host callback, so two
    streamed indexes never share a compiled closure — mirrored in the
    serving layer's compile-cache key
    (``serve.compile_cache.geometry_of``). The fetcher participates in
    the lru key by identity, which is exactly the sharing rule we want;
    the compiled filter (frozen tuples) participates by value, one
    executable per distinct predicate.
    """
    kwargs = _impl_kwargs(params, capacity, mode)
    rows, lanes = fetcher.record_shape

    def fetch(ids: jnp.ndarray) -> jnp.ndarray:
        # expand_dims: under vmap the fetcher is called ONCE per hop with
        # the batch axis leading, not once per query
        return jax.pure_callback(
            fetcher,
            jax.ShapeDtypeStruct(ids.shape + (rows, lanes), jnp.float32),
            ids,
            vmap_method="expand_dims",
        )

    @jax.jit
    def fn(queries, data, valid, meta=None):
        return _batch_search_impl(
            queries, data, valid, fetch=fetch, meta=meta, cfilter=cfilter,
            **kwargs,
        )

    return fn


def stream_search(
    queries: jnp.ndarray,
    data: SearchData,
    params: SearchParams,
    *,
    capacity: int,
    mode: str,
    fetcher,
    meta: MetaArrays | None = None,
    cfilter: CompiledFilter | None = None,
) -> SearchResult:
    """``batch_search`` over a budgeted index: ``data.page_recs`` holds
    only the resident page subset, and each hop's misses are pulled from
    the host memmap by ``fetcher`` (a ``core.stream.PageFetcher``) through
    a batched ``pure_callback`` — ONE host round-trip per hop for the
    whole query batch.

    Results are bit-identical to the fully resident ``batch_search`` on
    the same artifact: the staged batch is scored by
    ``kernels.ops.page_scan_recs`` with the same per-record compute, and
    every counter in ``SearchResult`` (ios/hops/cache_hits) is carried
    on-device independent of residency. (Host-side fetch counters are a
    superset of the useful reads — a vmapped while_loop keeps converged
    queries in the body until the whole batch exits, and their discarded
    hops still fetch.)
    """
    fn = _stream_search_fn(fetcher, params, capacity, mode, cfilter)
    valid = jnp.ones((queries.shape[0],), bool)
    return fn(queries, data, valid, meta)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk_streams(
    ids_a: jnp.ndarray,
    d_a: jnp.ndarray,
    ids_b: jnp.ndarray,
    d_b: jnp.ndarray,
    *,
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge two per-query top-k result streams into one (Q, k) top-k.

    The fresh+disk unification point of the mutable index
    (``repro.core.delta``): stream *a* is the persisted page-file search
    (tombstones already masked to PAD/INF), stream *b* the in-memory delta
    scan. Both are (Q, ka) / (Q, kb) ascending-by-distance with PAD ids
    carrying INF distances; the merge is one batched ``lax.top_k`` over the
    concatenation — same selection rule as the hot loop's ``merge`` — and
    re-masks non-finite winners to PAD so padding never leaks as a result.
    Returns (ids (Q, k) int32, dists (Q, k) f32).
    """
    d = jnp.concatenate([d_a, d_b], axis=1)
    ids = jnp.concatenate([ids_a, ids_b], axis=1).astype(jnp.int32)
    neg, idx = jax.lax.top_k(-d, k)
    merged = jnp.take_along_axis(ids, idx, axis=1)
    return jnp.where(jnp.isfinite(neg), merged, PAD), -neg


# --------------------------------------------------------------------------
# profiling entry point: the same transitions, with the per-hop trail kept
# --------------------------------------------------------------------------

class HopProfile(NamedTuple):
    """Per-hop trail of a profiled search (leading dims (Q, max_hops)).

    Hops past a query's exit carry ``active=False`` with PAD pages and
    zero deltas — fixed shape, mask to read. ``worst_topk`` is the worst
    running top-k distance *after* the hop (the early-termination
    frontier signal); ``stall`` is the adaptive patience counter (all
    zeros when the params are non-adaptive).
    """

    pages: jnp.ndarray       # (Q, H, b) page ids scheduled, PAD padded
    ios: jnp.ndarray         # (Q, H) disk page reads this hop
    cache_hits: jnp.ndarray  # (Q, H) cached page reads this hop
    active: jnp.ndarray      # (Q, H) bool: did the lane actually hop
    worst_topk: jnp.ndarray  # (Q, H) f32 running worst top-k distance
    stall: jnp.ndarray       # (Q, H) int32 patience counter after the hop


def _profile_one(
    q: jnp.ndarray,
    valid: jnp.ndarray,
    data: SearchData,
    *,
    capacity: int,
    beam: int,
    io_batch: int,
    k: int,
    max_hops: int,
    entries: int,
    mode: str,
    fetch=None,
    patience: int | None = None,
    epsilon: float = 0.0,
    entry_slack: int | None = None,
    min_entries: int = 1,
    meta: MetaArrays | None = None,
    cfilter: CompiledFilter | None = None,
):
    """``_search_one`` with the per-hop trail recorded.

    A ``lax.scan`` over ``max_hops`` replaces the ``while_loop``, calling
    the SAME pure transitions (``select_batch`` -> ``score_page_batch``
    -> ``merge``) and replicating the loop semantics explicitly: each
    step evaluates the while-cond, runs the body, and keeps the new state
    only where the cond held — the per-lane freeze vmap applies to a
    while_loop. ``_search_one`` itself is untouched, so the non-profiled
    path still traces the exact pre-profiling program.
    """
    disk_lut = pq_mod.pq_lut(q, data.disk_codebooks)
    mem_lut = (
        pq_mod.pq_lut(q, data.mem_codebooks)
        if mode != MemoryMode.DISK_ONLY.value
        else None
    )
    state = init_state(
        q, data, disk_lut, beam=beam, k=k, entries=entries,
        entry_slack=entry_slack, min_entries=min_entries, patience=patience,
    )

    def cond(state: BeamState):
        live = (
            (~state.cand_vis)
            & (state.cand_ids != PAD)
            & jnp.isfinite(state.cand_d)
        )
        go = live.any() & (state.hops < max_hops) & valid
        if patience is not None:
            go = go & (state.stall < patience)
        return go

    def step(state: BeamState, _):
        active = cond(state)
        st, batch = select_batch(
            state, capacity=capacity, io_batch=io_batch
        )
        mids, md, nids, nd, io_delta, hit_delta = score_page_batch(
            q, data, batch, st, disk_lut, mem_lut,
            capacity=capacity, mode=mode, fetch=fetch,
            meta=meta, cfilter=cfilter,
        )
        st = merge(
            st, mids, md, nids, nd, io_delta, hit_delta,
            patience=patience, epsilon=epsilon,
        )
        new = jax.tree.map(
            lambda a, b: jnp.where(active, b, a), state, st
        )
        rec = (
            jnp.where(active, batch, PAD),
            jnp.where(active, io_delta, 0).astype(jnp.int32),
            jnp.where(active, hit_delta, 0).astype(jnp.int32),
            active,
            new.res_d[k - 1],
            new.stall if patience is not None else jnp.int32(0),
        )
        return new, rec

    final, (pages, ios, hits, active, worst, stall) = jax.lax.scan(
        step, state, None, length=max_hops
    )
    return (
        (final.res_ids, final.res_d, final.io, final.hops, final.cache_hits),
        (pages, ios, hits, active, worst, stall),
    )


@functools.partial(
    jax.jit, static_argnames=("params", "capacity", "mode", "cfilter")
)
def profile_search(
    queries: jnp.ndarray,
    data: SearchData,
    params: SearchParams,
    *,
    capacity: int,
    mode: str,
    meta: MetaArrays | None = None,
    cfilter: CompiledFilter | None = None,
) -> tuple[SearchResult, HopProfile]:
    """``batch_search`` plus the per-hop trail (opt-in debug mode).

    Same arguments, same selection semantics: the profile run reuses the
    hop transitions verbatim, so scheduled pages, IO counters, hops and
    result ids match ``batch_search`` exactly (distances match up to XLA
    fusion reassociation across the scan-vs-while program boundary).
    This is a SEPARATE traced program — calling it never touches the
    compiled fast path's cache entries or its codegen.
    """
    valid = jnp.ones((queries.shape[0],), bool)
    fn = functools.partial(
        _profile_one, data=data, meta=meta, cfilter=cfilter,
        **_impl_kwargs(params, capacity, mode),
    )
    res, trail = jax.vmap(lambda q, v: fn(q, v))(queries, valid)
    ids, dists, ios, hops, hits = res
    pages, hio, hhits, active, worst, stall = trail
    return (
        SearchResult(ids=ids, dists=dists, ios=ios, hops=hops,
                     cache_hits=hits),
        HopProfile(pages=pages, ios=hio, cache_hits=hhits, active=active,
                   worst_topk=worst, stall=stall),
    )


# --------------------------------------------------------------------------
# mesh-sharded entry point: shard the query batch, replicate the index
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _shard_search_fn(
    mesh, params: SearchParams, capacity: int, mode: str,
    cfilter: CompiledFilter | None = None, with_meta: bool = False,
):
    """jitted shard_map: queries split over every mesh axis, data replicated.

    Cached per (mesh, params, capacity, mode, cfilter, with_meta) so
    repeated serving calls reuse the compiled executable. Filtered
    dispatches replicate the metadata columns like the index arrays
    (``with_meta``); the no-filter entry builds the exact pre-filter
    shard_map signature.
    """
    axes = tuple(mesh.axis_names)
    local = functools.partial(
        _batch_search_impl, **_impl_kwargs(params, capacity, mode)
    )
    data_spec = jax.tree.map(
        lambda _: P(), SearchData(*[0] * len(SearchData._fields))
    )
    if with_meta:
        def local_meta(queries, data, valid, meta):
            return local(queries, data, valid, meta=meta, cfilter=cfilter)

        fn = compat.shard_map(
            local_meta,
            mesh=mesh,
            in_specs=(P(axes), data_spec, P(axes), MetaArrays(P(), P())),
            out_specs=P(axes),
        )
    else:
        fn = compat.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axes), data_spec, P(axes)),
            out_specs=P(axes),
        )
    return jax.jit(fn)


def shard_search(
    queries: jnp.ndarray,
    data: SearchData,
    params: SearchParams,
    *,
    mesh=None,
    capacity: int,
    mode: str,
    meta: MetaArrays | None = None,
    cfilter: CompiledFilter | None = None,
) -> SearchResult:
    """``batch_search`` with the query batch sharded across a device mesh.

    The index (``data``) is replicated on every device; the (Q, d) query
    batch is split over all mesh axes — the paper's "query threads"
    throughput dimension mapped onto chips. Ragged batches are zero-padded
    to a multiple of the mesh size; the pad rows carry ``valid=False`` so
    their while_loop exits at hop 0 (no wasted full searches) and are
    trimmed from the result. On a 1-device mesh with no padding this runs
    the exact ``_batch_search_impl`` trace, so ids and distances are
    bitwise identical to ``batch_search``. (Index sharding — partitioning
    the vectors themselves — is the orthogonal axis and lives in
    ``core.distributed``.)
    """
    if mesh is None:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh()
    fn = _shard_search_fn(
        mesh, params, capacity, mode, cfilter, meta is not None
    )
    num_dev = 1
    for n in mesh.shape.values():
        num_dev *= n
    qn = queries.shape[0]
    pad = (-qn) % num_dev
    valid = jnp.ones((qn,), bool)
    if pad:
        queries = jnp.concatenate(
            [queries, jnp.zeros((pad, queries.shape[1]), queries.dtype)]
        )
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
    res = fn(queries, data, valid, meta) if meta is not None else fn(
        queries, data, valid
    )
    if pad:
        res = jax.tree.map(lambda a: a[:qn], res)
    return res


