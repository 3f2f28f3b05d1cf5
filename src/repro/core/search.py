"""PageANN graph search — Algorithm 2, as a fixed-shape JAX program.

Per query the loop maintains a :class:`BeamState`:
  * a candidate set (size-L, distance-sorted, visited flags) over *vector*
    ids in the reassigned space (page = id // capacity),
  * the trail of pages scheduled at each hop, (max_hops, b) and PAD
    padded: the paper's visited set V, whose size the search's knobs fix
    whatever the corpus's,
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
is one ``lax.sort`` + segment-boundary mask, and every membership test is
one dense equality compare (``dense_isin``): a hop's b*Rp neighbour ids
against the (L,) beam, and the pages of the beam's candidates and of the
hop's neighbours against the trail. That is O(b*Rp*(L + H*b)) compares, a
few microseconds of vector work, where a sorted ``searchsorted`` probe
lowers to a ``while`` loop of dependent gathers (on a TPU v5e it held 35%
of the resident search's device time: io batch 5, 48 neighbour slots a
page, beam 96) and a per-lane (P,) bitmap to element gathers and scatters
that grow with the corpus (19% there).

Everything is fixed-shape: the loop is a ``lax.while_loop``, queries are
vmapped (``batch_search``) and optionally sharded over a device mesh
(``shard_search`` — pad rows carry ``valid=False`` and exit at hop 0).
Runtime knobs (beam L, io batch b, max hops, LSH top-T, k) arrive per call
as a frozen :class:`repro.core.config.SearchParams` used as a static jit
argument — one compiled executable per distinct value, over one index.
I/O and cache-hit counters reproduce the paper's "Mean I/Os" metric.
Later async-prefetch / cache-eviction work should extend the transition
functions, not re-inline the loop.

Each stage of a hop runs under a ``jax.named_scope``, so
a device op's scope in a profiler trace names its stage: ``hop_select``,
``hop_scan`` (the page-scan kernels and their member masking),
``hop_fetch`` (the streamed path's host callback alone),
``hop_cache_probe``, ``hop_nbr_adc``, ``hop_cand_probe``, ``hop_dedupe``
and ``hop_merge``; a filtered search evaluates its predicate over a
hop's pages in ``hop_filter``. ``batch_search`` also keeps each lane's scheduled
pages per hop and reports, per query, the reads of a page that a
lower-numbered lane scanned at the same hop (``SearchResult.shared_reads``):
what a hop kernel that reads each shared page once would save. That
count runs under a scope of its own, ``shared_reads``.

Filtered search carries its predicate as data: the static
:class:`repro.core.filter.FilterShape` keys the program, and each query's
codes and numeric bounds (:class:`FilterValues`) are vmapped beside the
queries, so one executable serves every predicate of a shape. The exact
path of a selective predicate (``core.plan``) bypasses the graph:
``gather_members`` and ``posting_scan`` score the vectors the posting
lists name, in scope ``posting_scan``.
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
from repro.core.filter import FilterShape, MetaArrays, filter_mask
from repro.core.layout import MemoryTier, PageStore
from repro.core.lsh import LSHIndex, hash_codes
from repro.kernels import ops
from repro.kernels.record_layout import rows_per_vector, vectors_per_row

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
    # (d,) point the LSH hyperplanes pass through; None where they pass
    # through the origin (see core.lsh.build_lsh)
    lsh_center: jnp.ndarray | None = None


class FilterValues(NamedTuple):
    """Per-query values of a compiled filter of one ``FilterShape``:
    ``codes`` (Q, n_codes) int32 and ``bounds`` (Q, n_num, 2) float32
    (``CompiledFilter.codes`` / ``.bounds`` stacked)."""

    codes: jnp.ndarray
    bounds: jnp.ndarray


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
        lsh_center=lsh.center,
    )


class SearchResult(NamedTuple):
    ids: jnp.ndarray      # (Q, k) reassigned vector ids
    dists: jnp.ndarray    # (Q, k) exact squared distances
    ios: jnp.ndarray      # (Q,) page reads that went to 'disk'
    hops: jnp.ndarray     # (Q,) while_loop iterations
    cache_hits: jnp.ndarray  # (Q,) page reads served by the warmed cache
    # (Q,) page reads (ios + cache hits) of a page that a lower-numbered
    # query of the same batch read at the same hop; None where the search
    # keeps no per-hop trail (profile_search, merged or sharded results)
    shared_reads: jnp.ndarray | None = None


class BeamState(NamedTuple):
    """Per-query loop state of Algorithm 2 (one pytree, while_loop carry).

    The two adaptive fields are ``None`` — absent from the pytree — unless
    per-query early termination is on (``AdaptiveParams.patience``), so the
    non-adaptive loop carries the exact pre-adaptive structure and compiles
    to the same program.

    ``trail`` is the visited set V: row h holds the pages scheduled at hop
    h, and the rows from ``hops`` on are PAD, which no page id matches. A
    page is visited exactly when it is in the trail, so each visited test
    is one dense compare against it, and nothing in the state grows with
    the page count.
    """

    cand_ids: jnp.ndarray   # (L,) candidate vector ids, PAD padded
    cand_d: jnp.ndarray     # (L,) estimated distances, INF padded
    cand_vis: jnp.ndarray   # (L,) expanded/scheduled flags
    trail: jnp.ndarray      # (max_hops, b) pages scheduled per hop, PAD padded
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


@jax.named_scope("shared_reads")
def _shared_reads(trail: jnp.ndarray) -> jnp.ndarray:
    """(Q,) reads of a page that a lower-numbered lane read at the same hop,
    from the lanes' (Q, H, b) trails of scheduled pages.

    A stable sort of each hop's Q*b reads by page lines up a page's reads
    in lane order, so every read but the first of its run is shared (a
    lane schedules a page at most once a hop); a scatter to each read's
    own position puts the flags back in lane order. O(Q*b log(Q*b)) a hop
    (a second sort in the scatter's place adds seconds to a TPU compile).
    """
    q, h, b = trail.shape
    reads = jnp.swapaxes(trail, 0, 1).reshape(h, q * b)   # read j: lane j // b
    pos = jax.lax.broadcasted_iota(jnp.int32, reads.shape, 1)
    pages, pos = jax.lax.sort((reads, pos), num_keys=1, is_stable=True)
    shared = (pages[:, 1:] == pages[:, :-1]) & (pages[:, 1:] != PAD)
    shared = jnp.pad(shared, ((0, 0), (1, 0))).astype(jnp.int32)
    hop = jax.lax.broadcasted_iota(jnp.int32, reads.shape, 0)
    shared = jnp.zeros_like(shared).at[hop, pos].set(
        shared, unique_indices=True)
    return shared.reshape(h, q, b).sum((0, 2))


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
    max_hops: int,
    io_batch: int,
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
    qh = q if data.lsh_center is None else q - data.lsh_center
    qcode = hash_codes(qh[None], data.lsh_planes)[0]
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
        trail=jnp.full((max_hops, io_batch), PAD, jnp.int32),
        res_ids=jnp.full((k,), PAD, jnp.int32),
        res_d=jnp.full((k,), INF, jnp.float32),
        io=jnp.int32(0),
        cache_hits=jnp.int32(0),
        hops=jnp.int32(0),
        frontier=None if patience is None else jnp.float32(INF),
        stall=None if patience is None else jnp.int32(0),
    )


@jax.named_scope("hop_select")
def select_batch(
    state: BeamState, *, capacity: int, io_batch: int
) -> tuple[BeamState, jnp.ndarray]:
    """Pick up to b closest unvisited candidates whose pages are fresh.

    One vectorized pass replacing the seed's serial per-pick ``fori_loop``:
    stable-sort the beam by (masked distance, slot), keep the first
    occurrence of each page among finite entries, and take the first b —
    exactly the pages the iterated argmin would have scheduled, in the same
    order. Returns the updated state (selected candidates expanded, the
    batch written to the trail's row ``hops``, candidates on stale pages
    retired) and the (b,) batch of page ids to read, PAD padded.

    Both visited tests are dense compares of the candidates' pages: a page
    is stale when the trail holds it (its rows from this hop on are still
    PAD), and co-page candidates are expanded where the batch's first b-1
    pages hold it.
    """
    cand_ids = state.cand_ids
    beam = cand_ids.shape[0]
    b = io_batch

    cpages = jnp.where(cand_ids >= 0, cand_ids // capacity, 0)
    # retire candidates whose page was visited before this hop
    stale = (cand_ids != PAD) & dense_isin(cpages, state.trail)
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
    # expanded flags: the b scheduled picks, plus co-page candidates of any
    # page scheduled before the final pick (the serial loop's stale marking
    # ran once more after each pick except the last); the batch is in rank
    # order and its PAD slots match no page
    cand_vis = state.cand_vis | stale
    cand_vis = cand_vis.at[jnp.where(scheduled, sslot, beam)].set(
        True, mode="drop"
    )
    early = dense_isin(cpages, batch[:b - 1])
    cand_vis = cand_vis | ((cand_ids != PAD) & early)
    # the serial argmin marked slot 0 on every exhausted pick (all-INF mask)
    cand_vis = cand_vis.at[0].set(cand_vis[0] | (n_sched < b))
    # a select, not a scatter: cheaper in the vmapped loop
    at_hop = jnp.arange(state.trail.shape[0])[:, None] == state.hops
    trail = jnp.where(at_hop, batch[None, :], state.trail)
    return state._replace(cand_vis=cand_vis, trail=trail), batch


def dense_isin(ids: jnp.ndarray, pool: jnp.ndarray) -> jnp.ndarray:
    """(n,) membership of each id in ``pool`` (any shape), PAD included:
    one (n, pool.size) equality compare reduced over the pool, with no loop
    to lower and no gather."""
    return (ids[:, None] == pool.reshape(1, -1)).any(-1)


@jax.named_scope("hop_filter")
def page_member_mask(
    meta: MetaArrays, fshape: FilterShape, fvals: FilterValues,
    batch: jnp.ndarray, *, capacity: int,
) -> jnp.ndarray:
    """Evaluate one query's filter over one hop's page batch.

    ``meta`` holds page-slot-aligned metadata columns ((P*cap, T) tags /
    (P*cap, N) numerics — the same ``new_to_old`` layout the page records
    use), so a page's rows are one contiguous slice: gather the (b,)
    batch and evaluate the predicate (``fshape`` static, ``fvals`` this
    query's codes and bounds) to a (b, cap) f32 mask (1 = passes). Pad
    slots carry the missing sentinels (-1 / NaN) and never pass.
    """
    # explicit page count: a zero-width column block (schema with no tag
    # or no numeric fields) cannot infer it from a -1 reshape
    pages = meta.tags.shape[0] // capacity
    tags = meta.tags.reshape(pages, capacity, meta.tags.shape[-1])[batch]
    nums = meta.nums.reshape(pages, capacity, meta.nums.shape[-1])[batch]
    return filter_mask(fshape, fvals.codes, fvals.bounds, tags,
                       nums).astype(jnp.float32)


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
    fshape: FilterShape | None = None,
    fvals: FilterValues | None = None,
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

    With a filter bound (``meta``, ``fshape`` and this query's
    ``fvals``), the predicate is
    evaluated over the batch's page-slot-aligned metadata and pushed into
    the scan as a member mask: filtered-out members score ``+inf`` INSIDE
    the kernel, so the running result top-k only ever holds passing
    candidates. Neighbor ADC estimates stay unmasked — the graph must
    remain traversable through filtered-out regions to reach passing
    ones. With no filter both are ``None`` and the traced program is the
    exact pre-filter one.

    A neighbour is skipped where its page is in ``state.trail``, this
    hop's row included (one dense (b*Rp, H*b) compare), or its id in the
    beam, or it repeats an earlier neighbour of the hop.

    Returns (member_ids, member_dists) flattened to (b*cap,),
    (neighbor_ids, estimated_dists) flattened to (b*Rp,) and INF-masked,
    plus this hop's disk-I/O and cache-hit deltas.
    """
    cap = capacity
    rp = data.nbr_ids.shape[1]
    safe = jnp.maximum(batch, 0)
    fetched = batch >= 0
    scan = dict(capacity=cap, dim=q.shape[0], rp=rp,
                compute_adc=mode != MemoryMode.MEM_ALL.value)

    with jax.named_scope("hop_scan"):
        # the mask is a function of the page id alone, so the SAME (b, cap)
        # mask applies to the resident and staged lanes of the hop
        scan["member_mask"] = (
            page_member_mask(meta, fshape, fvals, safe, capacity=cap)
            if fshape is not None
            else None
        )
        if fetch is None:
            ex, est_disk = ops.page_scan(
                data.page_recs, safe, q, disk_lut, **scan
            )
        else:
            slot = data.resident_map[safe]                  # (b,)
            resident = slot >= 0
            # host fetch only what the device lacks; everything else
            # (resident pages, unselected PAD lanes) is masked to -1 and
            # comes back as a zero record whose scores are discarded by the
            # per-lane merge / downstream validity masks. The hook scopes
            # its host callback ``hop_fetch``
            missing = jnp.where(fetched & ~resident, safe, PAD)
            staged = fetch(missing)
            ex_r, est_r = ops.page_scan(
                data.page_recs, jnp.where(resident, slot, 0), q, disk_lut,
                **scan,
            )
            ex_s, est_s = ops.page_scan_recs(staged, q, disk_lut, **scan)
            ex = jnp.where(resident[:, None], ex_r, ex_s)
            est_disk = (
                None if est_r is None
                else jnp.where(resident[:, None], est_r, est_s)
            )
    with jax.named_scope("hop_scan"):
        slots = jnp.arange(cap)[None, :]
        ex = jnp.where(slots < data.member_count[safe][:, None], ex, INF)
        ex = jnp.where(fetched[:, None], ex, INF)
        member_ids = (batch[:, None] * capacity + slots).astype(jnp.int32)

    # warmed page cache (Sec 4.3): sorted-membership test
    if data.cached_pages.shape[0] > 0:
        with jax.named_scope("hop_cache_probe"):
            pos = jnp.searchsorted(data.cached_pages, safe)
            pos = jnp.minimum(pos, data.cached_pages.shape[0] - 1)
            in_cache = data.cached_pages[pos] == safe
    else:
        in_cache = jnp.zeros_like(fetched)
    io_delta = (fetched & ~in_cache).sum().astype(jnp.int32)
    hit_delta = (fetched & in_cache).sum().astype(jnp.int32)

    # neighbor estimates (Fig. 6 steps 3-4) per the coordination mode
    with jax.named_scope("hop_nbr_adc"):
        page_nids = data.nbr_ids[safe]                      # (b, Rp)
        flat_nids = page_nids.reshape(-1)                   # (b*Rp,)
        valid_n = (
            (jnp.arange(rp)[None, :] < data.nbr_count[safe][:, None])
            .reshape(-1)
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
            est = jnp.where(
                data.mem_mask[safe_nids], est_mem, est_disk.reshape(-1)
            )
        est = jnp.where(valid_n, est, INF)
        # skip neighbors on visited pages, this hop's included
        est = jnp.where(dense_isin(safe_nids // capacity, state.trail), INF,
                        est)
    # skip neighbors already in the candidate set
    with jax.named_scope("hop_cand_probe"):
        est = jnp.where(dense_isin(flat_nids, state.cand_ids), INF, est)
    # dedupe within this batch
    with jax.named_scope("hop_dedupe"):
        est = _mask_dups_keep_first(flat_nids, est)
    return member_ids.ravel(), ex.ravel(), flat_nids, est, io_delta, hit_delta


@jax.named_scope("hop_merge")
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
    fshape: FilterShape | None = None,
    fvals: FilterValues | None = None,
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
        max_hops=max_hops, io_batch=io_batch, entry_slack=entry_slack,
        min_entries=min_entries, patience=patience,
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
            meta=meta, fshape=fshape, fvals=fvals,
        )
        return merge(
            state, mids, md, nids, nd, io_delta, hit_delta,
            patience=patience, epsilon=epsilon,
        )

    state = jax.lax.while_loop(cond, body, state)
    return (state.res_ids, state.res_d, state.io, state.hops,
            state.cache_hits, state.trail)


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
    fshape: FilterShape | None = None,
    fvals: FilterValues | None = None,
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
        fshape=fshape,
    )
    if fshape is None:
        out = jax.vmap(fn)(queries, valid)
    else:
        # one query's codes and bounds ride beside it
        out = jax.vmap(lambda q, v, fv: fn(q, v, fvals=fv))(
            queries, valid, fvals)
    ids, dists, ios, hops, hits, trail = out
    return SearchResult(ids=ids, dists=dists, ios=ios, hops=hops,
                        cache_hits=hits, shared_reads=_shared_reads(trail))


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
    jax.jit, static_argnames=("params", "capacity", "mode", "fshape")
)
def batch_search(
    queries: jnp.ndarray,
    data: SearchData,
    params: SearchParams,
    *,
    capacity: int,
    mode: str,
    meta: MetaArrays | None = None,
    fshape: FilterShape | None = None,
    fvals: FilterValues | None = None,
) -> SearchResult:
    """Search a batch of queries. queries: (Q, d).

    ``params`` carries the per-call runtime knobs (beam L, io batch b,
    max hops, LSH top-T, k) and, being frozen/hashable, is a *static* jit
    argument: each distinct ``SearchParams`` value keys one compiled
    executable over the same built index. ``capacity`` and ``mode`` are
    build-time properties of the index artifact.

    Filtered search binds ``meta`` (page-slot-aligned metadata columns, a
    dynamic pytree), ``fshape`` (the predicates' static shape, one
    executable per shape) and ``fvals`` (each query's codes and bounds,
    (Q, ...) arrays vmapped beside the queries: any predicate of the
    shape, no recompile). All default to ``None``, and because ``meta``
    is an argument rather than a ``SearchData`` field, the no-filter call
    keeps the exact pre-filter jit signature and traces the identical
    program.
    """
    valid = jnp.ones((queries.shape[0],), bool)
    return _batch_search_impl(
        queries, data, valid, meta=meta, fshape=fshape, fvals=fvals,
        **_impl_kwargs(params, capacity, mode),
    )


# --------------------------------------------------------------------------
# streaming entry point: resident subset on device, misses fetched per hop
# --------------------------------------------------------------------------

def fetch_rungs(slots: int) -> tuple[int, ...]:
    """Sizes of the packed buffer a streamed hop may ship for ``slots``
    request slots: none, then an eighth, a quarter, half and all of them,
    each rounded up to a multiple of 8 (the record tile's sublanes)."""
    up = lambda n: -(-n // 8) * 8  # noqa: E731
    return tuple(sorted({0} | {up(-(-slots // d)) for d in (8, 4, 2, 1)}))


def _fetch_hook(fetcher):
    """The streamed hop's host fetch: ``fetch(missing)`` returns the
    records of a lane's (b,) ``missing`` page ids, zeros where PAD.

    Called by one lane, it is one ``pure_callback`` of the full shape.
    Under ``vmap`` (``batch_search``'s lanes) its rule sees the whole
    (Q, b) batch: it counts the live ids, packs them at the front of a
    buffer whose static size is the smallest rung of ``fetch_rungs(Q*b)``
    that holds them (a ``lax.switch`` on the count, with no batch axis),
    asks the host for that buffer alone, and expands it back to the
    lanes' slots. A hop with no live id makes no host call. Only the
    callback runs under ``hop_fetch``; the packing and the expansion run
    in the caller's scope.
    """
    rows, lanes = fetcher.record_shape

    def records(ids):
        with jax.named_scope("hop_fetch"):
            return jax.pure_callback(
                fetcher,
                jax.ShapeDtypeStruct(ids.shape + (rows, lanes), jnp.float32),
                ids,
            )

    @jax.custom_batching.custom_vmap
    def fetch(missing):
        return records(missing)

    @fetch.def_vmap
    def _(axis_size, in_batched, missing):
        if not in_batched[0]:
            missing = jnp.broadcast_to(missing, (axis_size,) + missing.shape)
        flat = missing.reshape(-1)
        live = flat >= 0
        n = live.sum()
        slot = jnp.cumsum(live) - 1       # a live id's place in the pack

        def rung(size):
            def branch():
                if size == 0:
                    return jnp.zeros(flat.shape + (rows, lanes), jnp.float32)
                ids = jnp.full((size,), PAD, flat.dtype).at[
                    jnp.where(live, slot, size)].set(flat, mode="drop")
                packed = records(ids)
                got = packed[jnp.clip(slot, 0, size - 1)]
                return jnp.where(live[:, None, None], got, 0.0)
            return branch

        rungs = fetch_rungs(flat.size)
        staged = jax.lax.switch(
            (n > jnp.asarray(rungs[:-1])).sum(), [rung(c) for c in rungs])
        return staged.reshape(missing.shape + (rows, lanes)), True

    return fetch


@functools.lru_cache(maxsize=32)
def _stream_search_fn(
    fetcher, params: SearchParams, capacity: int, mode: str,
    fshape: FilterShape | None = None,
):
    """jitted streaming search bound to one host fetcher.

    Cached per (fetcher, params, capacity, mode, fshape): the fetcher is
    baked into the executable as the hop body's host callback, so two
    streamed indexes never share a compiled closure — mirrored in the
    serving layer's compile-cache key
    (``serve.compile_cache.geometry_of``). The fetcher participates in
    the lru key by identity, which is exactly the sharing rule we want;
    the filter shape participates by value, one executable per shape.
    """
    kwargs = _impl_kwargs(params, capacity, mode)
    fetch = _fetch_hook(fetcher)

    @jax.jit
    def fn(queries, data, valid, meta=None, fvals=None):
        return _batch_search_impl(
            queries, data, valid, fetch=fetch, meta=meta, fshape=fshape,
            fvals=fvals, **kwargs,
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
    fshape: FilterShape | None = None,
    fvals: FilterValues | None = None,
) -> SearchResult:
    """``batch_search`` over a budgeted index: ``data.page_recs`` holds
    only the resident page subset, and each hop's misses are pulled from
    the host memmap by ``fetcher`` (a ``core.stream.PageFetcher``) through
    ``_fetch_hook``: at most ONE host round-trip per hop for the whole
    query batch, shipping only the records the hop asked for.

    Results are bit-identical to the fully resident ``batch_search`` on
    the same artifact: the staged batch is scored by
    ``kernels.ops.page_scan_recs`` with the same per-record compute, and
    every counter in ``SearchResult`` (ios/hops/cache_hits) is carried
    on-device independent of residency. (Host-side fetch counters are a
    superset of the useful reads — a vmapped while_loop keeps converged
    queries in the body until the whole batch exits, and their discarded
    hops still fetch.)
    """
    fn = _stream_search_fn(fetcher, params, capacity, mode, fshape)
    valid = jnp.ones((queries.shape[0],), bool)
    return fn(queries, data, valid, meta, fvals)


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
    fshape: FilterShape | None = None,
    fvals: FilterValues | None = None,
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
        max_hops=max_hops, io_batch=io_batch, entry_slack=entry_slack,
        min_entries=min_entries, patience=patience,
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
            meta=meta, fshape=fshape, fvals=fvals,
        )
        st = merge(
            st, mids, md, nids, nd, io_delta, hit_delta,
            patience=patience, epsilon=epsilon,
        )
        new = jax.tree.map(
            lambda a, b: jnp.where(active, b, a), state, st
        )
        rec = (
            jnp.where(active, io_delta, 0).astype(jnp.int32),
            jnp.where(active, hit_delta, 0).astype(jnp.int32),
            active,
            new.res_d[k - 1],
            new.stall if patience is not None else jnp.int32(0),
        )
        return new, rec

    # a lane's active steps are a prefix, so its step h wrote row h of
    # the trail: the trail is the per-hop pages, PAD past the exit
    final, (ios, hits, active, worst, stall) = jax.lax.scan(
        step, state, None, length=max_hops
    )
    return (
        (final.res_ids, final.res_d, final.io, final.hops, final.cache_hits),
        (final.trail, ios, hits, active, worst, stall),
    )


@functools.partial(
    jax.jit, static_argnames=("params", "capacity", "mode", "fshape")
)
def profile_search(
    queries: jnp.ndarray,
    data: SearchData,
    params: SearchParams,
    *,
    capacity: int,
    mode: str,
    meta: MetaArrays | None = None,
    fshape: FilterShape | None = None,
    fvals: FilterValues | None = None,
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
        _profile_one, data=data, meta=meta, fshape=fshape,
        **_impl_kwargs(params, capacity, mode),
    )
    if fshape is None:
        res, trail = jax.vmap(lambda q, v: fn(q, v))(queries, valid)
    else:
        res, trail = jax.vmap(lambda q, v, fv: fn(q, v, fvals=fv))(
            queries, valid, fvals)
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
    fshape: FilterShape | None = None, center: bool = False,
):
    """jitted shard_map: queries split over every mesh axis, data replicated.

    Cached per (mesh, params, capacity, mode, fshape, center) so
    repeated serving calls reuse the compiled executable. Filtered
    dispatches replicate the metadata columns like the index arrays and
    split the per-query filter values with the queries; the no-filter
    entry builds the exact pre-filter shard_map signature. ``center``
    says whether the index's LSH hyperplanes carry a centre.
    """
    axes = tuple(mesh.axis_names)
    local = functools.partial(
        _batch_search_impl, **_impl_kwargs(params, capacity, mode)
    )
    fields = [0] * len(SearchData._fields)
    if not center:
        fields[SearchData._fields.index("lsh_center")] = None
    data_spec = jax.tree.map(lambda _: P(), SearchData(*fields))
    if fshape is not None:
        def local_meta(queries, data, valid, meta, fvals):
            return local(queries, data, valid, meta=meta, fshape=fshape,
                         fvals=fvals)

        fn = compat.shard_map(
            local_meta,
            mesh=mesh,
            in_specs=(P(axes), data_spec, P(axes), MetaArrays(P(), P()),
                      FilterValues(P(axes), P(axes))),
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
    fshape: FilterShape | None = None,
    fvals: FilterValues | None = None,
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
        mesh, params, capacity, mode, fshape, data.lsh_center is not None
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
        if fvals is not None:
            fvals = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]), fvals)
    res = fn(queries, data, valid, meta, fvals) if fshape is not None else fn(
        queries, data, valid
    )
    if pad:
        res = jax.tree.map(lambda a: a[:qn], res)
    return res


# --------------------------------------------------------------------------
# exact path of a selective predicate: score the vectors its posting lists
# name (``core.plan``), no graph
# --------------------------------------------------------------------------

def gather_members(
    page_recs: jnp.ndarray,
    resident_map: jnp.ndarray,
    ids: jnp.ndarray,
    *,
    capacity: int,
    dim: int,
) -> jnp.ndarray:
    """(Q, B, dim) f32 member vectors of the reassigned ``ids`` (Q, B),
    read from the resident page records (every page resident): vector
    ``v`` sits in the record of page ``v // capacity`` at the slot
    ``v % capacity`` of the packing ``core.layout.pack_page_records``
    lays out. Whole 128-lane record rows are gathered (a row holds
    several vectors for dim <= 128, a vector spans several rows above),
    and the vector's lanes taken from them: a per-vector dynamic slice
    lowers on a TPU to a loop that held most of the chip's time. PAD ids
    read slot 0 of page 0; their scores are masked."""
    safe = jnp.maximum(ids, 0)
    slot = safe % capacity
    rows, lanes = page_recs.shape[1:]
    first = resident_map[safe // capacity] * rows         # the record's row
    flat = page_recs.reshape(-1, lanes)
    if dim <= lanes:
        vpr = vectors_per_row(dim)
        got = jnp.take(flat, first + slot // vpr, axis=0, mode="clip")
        got = got[..., :vpr * dim].reshape(*ids.shape, vpr, dim)
        pick = (slot % vpr)[..., None, None]
        return jnp.take_along_axis(got, pick, axis=-2)[..., 0, :]
    rpv = rows_per_vector(dim)
    row = (first + slot * rpv)[..., None] + jnp.arange(rpv)
    got = jnp.take(flat, row, axis=0, mode="clip")        # (Q, B, rpv, lanes)
    return got.reshape(*ids.shape, rpv * lanes)[..., :dim]


def _exact_top_k(queries, vecs, cand, row_query, first_row, n_rows,
                 k: int, slots: int):
    """Score each row's candidates against its query and keep the row's
    nearest k (ties to the lower position), then fold each query's rows'
    picks, in row order, into an empty result top-k with the hop's own
    ``merge``: the exact path is one hop whose members are the query's
    matches."""
    diff = vecs - queries[row_query][:, None, :]
    d = jnp.where(cand >= 0, jnp.sum(diff * diff, axis=-1), INF)   # (R, W)
    row_d, pos = _top_k_merge(d, k)                                # (R, k)
    row_ids = jnp.take_along_axis(cand, pos, axis=-1)
    j = jnp.arange(slots)
    own = j < n_rows[:, None]                                      # (Q, slots)
    at = jnp.where(own, first_row[:, None] + j, 0)
    q = queries.shape[0]
    mem_ids = jnp.where(own[..., None], row_ids[at], PAD).reshape(q, -1)
    mem_d = jnp.where(own[..., None], row_d[at], INF).reshape(q, -1)

    def fold(ids_q, d_q):
        none = jnp.zeros((0,), jnp.int32)
        # merge never reads the trail
        empty = BeamState(
            cand_ids=none, cand_d=none.astype(jnp.float32),
            cand_vis=none.astype(bool), trail=none.reshape(0, 0),
            res_ids=jnp.full((k,), PAD, jnp.int32),
            res_d=jnp.full((k,), INF, jnp.float32),
            io=jnp.int32(0), cache_hits=jnp.int32(0), hops=jnp.int32(0))
        out = merge(empty, ids_q, d_q, none, none.astype(jnp.float32),
                    jnp.int32(0), jnp.int32(0))
        return out.res_ids, out.res_d

    return jax.vmap(fold)(mem_ids, mem_d)


@functools.partial(jax.jit, static_argnames=("k", "capacity", "slots"))
def posting_scan(
    queries: jnp.ndarray,
    cand: jnp.ndarray,
    row_query: jnp.ndarray,
    first_row: jnp.ndarray,
    n_rows: jnp.ndarray,
    data: SearchData | None = None,
    vecs: jnp.ndarray | None = None,
    *,
    k: int,
    slots: int,
    capacity: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k of each query (Q, d) over its candidates, packed as
    rows: ``cand`` (R, W) holds reassigned ids (PAD = -1 never scores),
    each row one query's (``row_query`` (R,)), and query ``i``'s rows are
    the ``n_rows[i]`` (at most ``slots``) from ``first_row[i]`` on, its
    candidates in ascending original-id order. Their vectors are gathered
    from ``data``'s resident page records (``gather_members``), or given
    as ``vecs`` (R, W, d) where the pages are not all resident. Squared L2
    as sums of squared differences in float32 (no matmul, so no matmul
    precision to choose), ties to the lower candidate position
    (``lax.top_k``, per row and in ``merge``). Returns (ids (Q, k) int32,
    PAD where fewer than k candidates, dists (Q, k), INF there)."""
    with jax.named_scope("posting_scan"):
        if vecs is None:
            vecs = gather_members(data.page_recs, data.resident_map, cand,
                                  capacity=capacity, dim=queries.shape[1])
        return _exact_top_k(queries, vecs, cand, row_query, first_row,
                            n_rows, k, slots)
