"""High-level PageANN index: build / search / stats (Fig. 3 pipeline).

Pre-processing stage: Vamana vector graph -> page-node grouping (Alg. 1) ->
PQ codebooks (coarse on-page + fine in-memory) -> id reassignment + page
packing (Sec 4.2/5) -> LSH routing index -> memory-disk coordination
(Sec 4.3) with optional warm-up page caching.

Query stage: ``search`` wraps ``core.search.batch_search`` and translates
results back to original vector ids; runtime knobs arrive per call as a
:class:`repro.core.config.SearchParams` (one compiled executable per
distinct value — sweeps never rebuild the index).

Lifecycle: ``save(dir)`` / ``load(dir)`` persist the index through
``core.persist`` (raw page-aligned ``pages.bin`` + numpy sidecars + JSON
manifest); loading round-trips to bit-identical search results.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import filter as filter_mod
from repro.core import layout as layout_mod
from repro.core import lsh as lsh_mod
from repro.core import page_graph as pg_mod
from repro.core import plan as plan_mod
from repro.core import pq as pq_mod
from repro.core import search as search_mod
from repro.core import stream as stream_mod
from repro.core import vamana as vamana_mod
from repro.core.config import (
    AdaptiveParams,
    FilterParams,
    MemoryMode,
    PageANNConfig,
    SearchParams,
    resolve_search_params,
)
from repro.core.filter import FilterExpr, MetaArrays, MetadataSchema
from repro.core.plan import EXACT, QueryPlan
from repro.obs.trace import phase

PAD = -1


@dataclasses.dataclass
class BuildStats:
    vamana_s: float
    grouping_s: float
    pq_s: float
    pack_s: float
    lsh_s: float
    pages: int
    capacity: int
    mean_page_degree: float
    logical_page_bytes: int
    padded_tile_bytes: int
    memory_bytes: int
    # total bytes of the disk tier. For a freshly built index this is the
    # projected pages.bin size (pages * padded_tile_bytes); for an index
    # loaded via memmap, persist.load_pageann overwrites it with the actual
    # on-disk size of the persisted artifact — stats reports what the file
    # occupies, not a recomputation from device arrays. Defaults to 0 for
    # manifests written before the field existed.
    disk_bytes: int = 0
    # resident/streamed split of the disk tier on device: how many page
    # records are pinned in device memory and their byte footprint. Equal
    # to pages/disk_bytes when fully resident; smaller under a
    # ``MemoryBudget`` load, where the remainder streams from the pages.bin
    # memmap per hop. Default 0 for manifests written before streaming.
    resident_pages: int = 0
    resident_bytes: int = 0


@dataclasses.dataclass
class PageANNIndex:
    cfg: PageANNConfig
    store: layout_mod.PageStore
    tier: layout_mod.MemoryTier
    lsh: lsh_mod.LSHIndex
    data: search_mod.SearchData
    stats: BuildStats
    # streaming page tier (set by a ``MemoryBudget`` load, None otherwise):
    # the host-side per-hop reader over the pages.bin memmap
    fetcher: object | None = None
    # full residency priority, hottest page first (warm_cache access
    # counts); persisted so a budgeted load pins the right pages
    page_order: np.ndarray | None = None
    memory_budget: object | None = None
    # autotuned operating points (``autotune``): measured
    # {params, recall, qps, p99_us, target} dicts, persisted in the
    # manifest's ``tuned`` section; ``tuned_default`` is the point serving
    # resolves as this index's default SearchParams
    tuned: list = dataclasses.field(default_factory=list)
    tuned_default: SearchParams | None = None
    # filtered search (``core.filter``): the declared metadata schema, the
    # tag vocabularies (field -> tuple of values; codes are positions),
    # slot-aligned device columns the page scan gathers masks from, and
    # the original-order host copy (match counting / brute-force oracle /
    # compaction source). All None/empty without a schema.
    schema: MetadataSchema | None = None
    vocab: dict = dataclasses.field(default_factory=dict)
    meta: MetaArrays | None = None
    meta_host: MetaArrays | None = None
    # per tag value the ids carrying it (built from meta_host at build and
    # load), and what the per-query path choice depends on (core.plan)
    postings: filter_mod.Postings | None = dataclasses.field(
        default=None, repr=False)
    planner: plan_mod.Planner | None = dataclasses.field(
        default=None, repr=False)

    def __post_init__(self):
        if self.schema is not None and self.postings is None:
            self.postings = filter_mod.build_postings(
                self.schema, self.vocab, self.meta_host.tags)
        # value -> code per field, built once for every predicate's compile
        self._codes = filter_mod.code_maps(self.vocab)
        if self.planner is None:
            self.planner = plan_mod.Planner(
                live=self.store.num_vectors,
                capacity=self.store.capacity,
                rows_per_page=(self.store.padded_tile_bytes()
                               // (self.store.dim * 4)),
                device_bytes=_device_bytes(),
            )

    # ------------------------------------------------------------------ build
    @staticmethod
    def build(
        x: np.ndarray,
        cfg: PageANNConfig,
        mem_subspaces: int | None = None,
        warmup_queries: np.ndarray | None = None,
        schema: MetadataSchema | None = None,
        metadata=None,
    ) -> "PageANNIndex":
        x = np.ascontiguousarray(x, np.float32)
        n, d = x.shape
        assert d == cfg.dim

        t0 = time.perf_counter()
        nbrs = vamana_mod.build_vamana(
            x,
            degree=cfg.graph_degree,
            beam=cfg.build_beam,
            alpha=cfg.alpha,
            rounds=cfg.build_rounds,
            seed=cfg.seed,
        )
        t1 = time.perf_counter()

        capacity = cfg.resolve_capacity()
        grouping = pg_mod.group_pages(x, nbrs, capacity, cfg.hop_h)
        page_nbrs_old = pg_mod.derive_page_edges(x, nbrs, grouping, cfg.page_degree)
        t2 = time.perf_counter()

        # coarse codes travel on-page; fine codes live in the memory tier
        m_disk = cfg.pq_subspaces
        m_mem = mem_subspaces or min(d, 2 * m_disk)
        disk_books = pq_mod.train_pq(
            x, m_disk, cfg.pq_ksub, cfg.pq_iters, seed=cfg.seed
        )
        mem_books = pq_mod.train_pq(
            x, m_mem, cfg.pq_ksub, cfg.pq_iters, seed=cfg.seed + 1
        )
        disk_codes_old = np.asarray(
            pq_mod.pq_encode(jnp.asarray(x), jnp.asarray(disk_books))
        )
        t3 = time.perf_counter()

        store = layout_mod.pack_pages(x, grouping, page_nbrs_old, disk_codes_old, cfg)
        x_new = layout_mod.reassigned_vectors(x, store)
        mem_codes_new = np.asarray(
            pq_mod.pq_encode(jnp.asarray(x_new), jnp.asarray(mem_books))
        )
        t4 = time.perf_counter()

        lsh = lsh_mod.build_lsh(
            x_new,
            np.asarray(pq_mod.pq_encode(jnp.asarray(x_new), jnp.asarray(disk_books))),
            bits=cfg.lsh_bits,
            sample=cfg.lsh_sample,
            seed=cfg.seed,
        )
        t5 = time.perf_counter()

        tier = layout_mod.build_memory_tier(
            x_new, mem_codes_new, mem_books, disk_books, cfg.memory_mode
        )
        data = search_mod.make_search_data(store, tier, lsh)

        # metadata columns: encode in original-id order, scatter to page-
        # slot order alongside the member vectors
        if metadata is not None and schema is None:
            raise ValueError("metadata= requires a schema=")
        vocab: dict = {}
        meta = meta_host = None
        if schema is not None:
            columns = filter_mod.normalize_metadata(
                schema, metadata if metadata is not None else {}, n
            )
            vocab = filter_mod.build_vocab(schema, columns)
            meta_host = filter_mod.encode_metadata(schema, vocab, columns, n)
            slot_tags, slot_nums = layout_mod.reassign_metadata(
                meta_host.tags, meta_host.nums, store
            )
            meta = MetaArrays(
                tags=jnp.asarray(slot_tags), nums=jnp.asarray(slot_nums)
            )

        idx = PageANNIndex(
            cfg=cfg,
            store=store,
            tier=tier,
            lsh=lsh,
            data=data,
            stats=BuildStats(
                vamana_s=t1 - t0,
                grouping_s=t2 - t1,
                pq_s=t3 - t2,
                pack_s=t4 - t3,
                lsh_s=t5 - t4,
                pages=store.num_pages,
                capacity=capacity,
                mean_page_degree=pg_mod.page_graph_stats(
                    np.asarray(store.nbr_ids)
                )["mean_degree"],
                logical_page_bytes=store.logical_page_bytes(cfg),
                padded_tile_bytes=store.padded_tile_bytes(),
                memory_bytes=tier.memory_bytes + lsh.memory_bytes,
                disk_bytes=store.num_pages * store.padded_tile_bytes(),
                resident_pages=store.num_pages,
                resident_bytes=store.num_pages * store.padded_tile_bytes(),
            ),
            schema=schema,
            vocab=vocab,
            meta=meta,
            meta_host=meta_host,
        )
        if warmup_queries is not None and cfg.cache_pages > 0:
            idx.warm_cache(warmup_queries)
        return idx

    # ------------------------------------------------------------ properties
    @property
    def dim(self) -> int:
        return self.cfg.dim

    @property
    def default_params(self) -> SearchParams:
        """The runtime parameter set searches resolve when none is given:
        the autotuned operating point if one is stored (``autotune`` /
        the manifest's ``tuned.default``), else the build config's knobs."""
        if self.tuned_default is not None:
            return self.tuned_default
        return SearchParams.from_config(self.cfg)

    def resolve_params(
        self, k: int | None, params: SearchParams | None
    ) -> SearchParams:
        return resolve_search_params(self.default_params, k, params)

    # ------------------------------------------------------------------ cache
    def warm_cache(self, queries: np.ndarray, params: SearchParams | None = None) -> None:
        """Sec 4.3: run a warm-up batch, cache the hottest pages.

        Also records the FULL access ordering over all pages as
        ``page_order`` (accessed pages by descending count, then the never-
        accessed rest in id order) — the residency policy a budgeted
        ``load(..., memory_budget=...)`` pins pages by."""
        p = self.resolve_params(None, params)
        res = self._raw_search(jnp.asarray(queries, jnp.float32), p)
        pages = np.asarray(res.ids) // self.store.capacity
        pages = pages[np.asarray(res.ids) >= 0]
        uniq, counts = np.unique(pages, return_counts=True)
        by_heat = uniq[np.argsort(-counts)].astype(np.int32)
        hot = by_heat[: self.cfg.cache_pages]
        cold = np.setdiff1d(
            np.arange(self.store.num_pages, dtype=np.int32), by_heat
        )
        self.page_order = np.concatenate([by_heat, cold])
        self.tier = dataclasses.replace(
            self.tier, cached_pages=jnp.asarray(np.sort(hot).astype(np.int32))
        )
        self.data = search_mod.make_search_data(self.store, self.tier, self.lsh)

    # ----------------------------------------------------------------- search
    def _raw_search(
        self, q: jnp.ndarray, params: SearchParams, mesh=None,
        meta=None, fshape=None, fvals=None,
    ) -> search_mod.SearchResult:
        if mesh is not None:
            if self.fetcher is not None:
                raise ValueError(
                    "sharded search over a streamed (memory-budgeted) index "
                    "is not supported: reload without memory_budget to "
                    "search across a mesh"
                )
            return search_mod.shard_search(
                q, self.data, params,
                mesh=mesh,
                capacity=self.store.capacity,
                mode=self.cfg.memory_mode.value,
                meta=meta, fshape=fshape, fvals=fvals,
            )
        if self.fetcher is not None:
            return search_mod.stream_search(
                q, self.data, params,
                capacity=self.store.capacity,
                mode=self.cfg.memory_mode.value,
                fetcher=self.fetcher,
                meta=meta, fshape=fshape, fvals=fvals,
            )
        return search_mod.batch_search(
            q, self.data, params,
            capacity=self.store.capacity,
            mode=self.cfg.memory_mode.value,
            meta=meta, fshape=fshape, fvals=fvals,
        )

    # ----------------------------------------------------------------- filter
    def _matches(self, expr: FilterExpr):
        """(CompiledFilter, ascending original ids passing ``expr``)."""
        cf = filter_mod.compile_filter(expr, self.schema, self._codes)
        return cf, filter_mod.match_ids(
            cf, self.postings, self.meta_host.tags, self.meta_host.nums)

    def compiled_filter(self, expr: FilterExpr):
        """Resolve a ``FilterExpr`` against this index's schema/vocab and
        count its matches from the posting lists. Returns
        (CompiledFilter, selectivity: the fraction of vectors passing)."""
        cf, ids = self._matches(expr)
        return cf, len(ids) / max(1, self.planner.live)

    def plan(
        self,
        expr: FilterExpr,
        k: int | None = None,
        params: SearchParams | None = None,
        filter_params: FilterParams | None = None,
    ) -> QueryPlan:
        """Choose the path of one query filtered by ``expr`` from its match
        count (``core.plan``): the exact scan of its matches, or the graph
        with the beam oversampled for its selectivity. Queries whose
        plans share a ``group`` share one compiled program and dispatch."""
        return self.plan_many([expr], k, params, filter_params)[0]

    def plan_many(
        self,
        exprs,
        k: int | None = None,
        params: SearchParams | None = None,
        filter_params: FilterParams | None = None,
    ) -> list:
        """:meth:`plan` of each of ``exprs`` (a None stays None), with the
        params, the threshold and each distinct predicate resolved once."""
        p = self.resolve_params(k, params)
        fp = filter_params if filter_params is not None else FilterParams()
        cap = fp.max_filter_oversample
        threshold = self.planner.threshold(p, cap)
        done: dict = {}
        for expr in exprs:
            if expr is not None and expr not in done:
                cf, ids = self._matches(expr)
                done[expr] = self.planner.plan(
                    cf, ids, self.store.old_to_new, p, cap, threshold)
        return [None if e is None else done[e] for e in exprs]

    def _plans(self, filter, n: int, p: SearchParams,
               fp: FilterParams | None) -> list:
        """One plan per query: ``filter`` is one ``FilterExpr`` for every
        query, or a sequence of ``n`` ``FilterExpr``/``QueryPlan``s, or
        None for a row whose answer is not wanted (a batch's padding)."""
        if isinstance(filter, FilterExpr):
            return self.plan_many([filter], params=p, filter_params=fp) * n
        filter = list(filter)
        if len(filter) != n:
            raise ValueError(f"{len(filter)} filters for {n} queries")
        for f in filter:
            if not (f is None or isinstance(f, (QueryPlan, FilterExpr))):
                raise TypeError(f"a per-query filter is a FilterExpr, a "
                                f"QueryPlan or None, not "
                                f"{type(f).__name__}")
        exprs = [f if isinstance(f, FilterExpr) else None for f in filter]
        if not any(e is not None for e in exprs):
            return filter
        planned = self.plan_many(exprs, params=p, filter_params=fp)
        return [f if e is None else pl
                for f, e, pl in zip(filter, exprs, planned)]

    def _exact_scan(self, q: np.ndarray, cand: np.ndarray,
                    row_query: np.ndarray, first_row: np.ndarray,
                    n_rows: np.ndarray, k: int, slots: int):
        """``search.posting_scan`` of packed candidate rows: their vectors
        gathered on the device from the resident page records, or, for a
        streamed index, from the host copy of the members."""
        if self.fetcher is None:
            return search_mod.posting_scan(
                q, cand, row_query, first_row, n_rows, self.data, k=k,
                slots=slots, capacity=self.store.capacity)
        flat = self.store.vecs.reshape(-1, self.store.dim)
        return search_mod.posting_scan(
            q, cand, row_query, first_row, n_rows,
            vecs=flat[np.maximum(cand, 0)], k=k, slots=slots)

    def _exact_search(self, q: np.ndarray, plans: list, k: int,
                      slots: int):
        """The exact path of one group, one program whatever its queries'
        match counts: each query's matches (a None plan has none) fill
        whole rows of ``row_width(k)`` candidates, the queries' rows lie
        back to back, padded to ``packed_rows`` of their count
        (``core.plan``), and ``posting_scan`` scores them. Returns its
        (ids, dists), not yet fetched from the device."""
        width = plan_mod.row_width(k)
        n_rows = np.array([0 if pl is None else pl.bucket for pl in plans],
                          np.int32)
        if n_rows.max(initial=0) > slots:
            raise ValueError(f"an exact-path plan of {n_rows.max()} rows; "
                             f"the exact program takes at most {slots}")
        first = (np.cumsum(n_rows) - n_rows).astype(np.int32)
        total = int(n_rows.sum())
        rows = plan_mod.packed_rows(total)
        cand = np.full(rows * width, PAD, np.int32)
        for at, pl in zip(first * width, plans):
            if pl is not None:
                cand[at:at + pl.count] = pl.matches
        row_query = np.zeros(rows, np.int32)
        row_query[:total] = np.repeat(np.arange(len(plans), dtype=np.int32),
                                      n_rows)
        return self._exact_scan(q, cand.reshape(rows, width), row_query,
                                first, n_rows, k, slots)

    def _filtered_search(self, q, p: SearchParams, filter, fp, mesh):
        """Plan each query of ``q`` ((Q, d) numpy), then run each group
        (``core.plan``): an exact group as one program over the batch's
        lanes, a graph group in chunks of ``GRAPH_LANES`` padded with
        copies of its first row. Every program is dispatched before any
        result is fetched, so the device runs them back to back. Rows
        planned None get PAD answers."""
        n = q.shape[0]
        fp = fp if fp is not None else FilterParams()
        with phase("filter.plan", cat="search", track="search", n=n):
            plans = self._plans(filter, n, p, fp)
            groups: dict = {}
            for i, pl in enumerate(plans):
                if pl is not None:
                    groups.setdefault(pl.group, []).append(i)
        out = dict(ids=np.full((n, p.k), PAD, np.int32),
                   dists=np.full((n, p.k), np.inf, np.float32),
                   **{f: np.zeros(n, np.int32) for f in (
                       "ios", "hops", "cache_hits", "shared_reads")})
        running = []
        for group, rows in groups.items():
            if group[1] == EXACT:
                mine = set(rows)
                top, dists = self._exact_search(
                    q, [pl if i in mine else None
                        for i, pl in enumerate(plans)],
                    p.k, self.planner.slots(p, fp.max_filter_oversample))
                running.append((rows, np.asarray(rows),
                                {"ids": top, "dists": dists}))
                continue
            shape, _, bucket = group
            lanes = plan_mod.GRAPH_LANES
            for lo in range(0, len(rows), lanes):
                part = rows[lo:lo + lanes]
                take = part + part[:1] * (lanes - len(part))
                sub = [plans[i] for i in take]
                vals = search_mod.FilterValues(
                    codes=np.stack([s.filter.codes for s in sub]),
                    bounds=np.stack([s.filter.bounds for s in sub]))
                res = self._raw_search(
                    jnp.asarray(q[take]),
                    p.replace(beam_width=p.beam_width * bucket),
                    mesh=mesh, meta=self.meta, fshape=shape, fvals=vals)
                running.append((part, slice(len(part)), {
                    f: getattr(res, f) for f in out
                    if getattr(res, f) is not None}))
        for part, at, got in running:
            for f, v in got.items():
                out[f][part] = np.asarray(v)[at]
        return search_mod.SearchResult(**out)

    def metadata_by_original_id(self) -> dict[str, list] | None:
        """Decoded metadata columns in ORIGINAL id order (missing ->
        None) — what a compaction merges with the delta tier's fresh
        metadata before re-encoding under a new vocabulary. ``None``
        when the index has no schema."""
        if self.schema is None:
            return None
        return filter_mod.decode_metadata(
            self.schema, self.vocab, self.meta_host
        )

    def fetch_stats(self) -> dict:
        """Streaming-tier counters (``pages_fetched`` / ``fetch_hits`` /
        ``fetch_wall_s`` / ``fetch_calls`` / ``staged_slots``); zeros when
        fully resident."""
        if self.fetcher is None:
            return stream_mod.no_fetch_stats()
        return self.fetcher.fetch_stats()

    def vectors_by_original_id(self) -> np.ndarray:
        """Member vectors in ORIGINAL id order: the inverse of the build's
        page packing/id reassignment, recovered from the page store (which
        holds the vectors verbatim as f32 — exact round trip). This is the
        dataset a compaction (``core.delta``) merges fresh inserts into."""
        flat = np.asarray(self.store.vecs).reshape(-1, self.store.dim)
        valid = self.store.new_to_old >= 0
        out = np.empty((self.store.num_vectors, self.store.dim), np.float32)
        out[self.store.new_to_old[valid]] = flat[valid]
        return out

    def translate_ids(self, ids: np.ndarray) -> np.ndarray:
        """Reassigned (page-packed) vector ids -> original ids, PAD kept."""
        ids = np.asarray(ids)
        valid = ids >= 0
        old = np.full_like(ids, PAD)
        old[valid] = self.store.new_to_old[ids[valid]]
        return old

    def search(
        self,
        queries: np.ndarray,
        k: int | None = None,
        params: SearchParams | None = None,
        *,
        mesh=None,
        filter: FilterExpr | None = None,
        filter_params: FilterParams | None = None,
    ) -> search_mod.SearchResult:
        """Search; returns ORIGINAL vector ids.

        ``params`` supplies the runtime knobs (defaults come from the build
        config); ``k`` overrides ``params.k`` when given. Passing a device
        mesh routes through ``shard_search`` (query batch split across it).

        ``filter`` restricts results to vectors whose metadata satisfies
        the predicate (see ``core.filter``): one ``FilterExpr`` for every
        query, or a sequence of one per query (``FilterExpr``, a
        ``QueryPlan`` from :meth:`plan`, or None for a padding row whose
        answer is not wanted: it gets PAD ids). Each query takes its own path
        (``core.plan``): a selective predicate's matches are scanned
        exactly, a broad one's run through the graph with the predicate
        masking members to ``+inf`` inside the page scan and the beam
        widened by a pow2 factor of its selectivity (bounded by
        ``filter_params.max_filter_oversample``). Predicates of one shape
        share one compiled program per path and bucket. ``filter=None``
        compiles and runs the exact pre-filter program.
        """
        p = self.resolve_params(k, params)
        if filter is not None:
            res = self._filtered_search(np.asarray(queries, np.float32), p,
                                        filter, filter_params, mesh)
        else:
            res = self._raw_search(jnp.asarray(queries, jnp.float32), p,
                                   mesh=mesh)
        return search_mod.SearchResult(
            ids=self.translate_ids(res.ids),
            dists=np.asarray(res.dists),
            ios=np.asarray(res.ios),
            hops=np.asarray(res.hops),
            cache_hits=np.asarray(res.cache_hits),
            shared_reads=np.asarray(res.shared_reads),
        )

    def precompile_filtered(
        self,
        example: FilterExpr,
        *,
        batch_size: int,
        k: int | None = None,
        params: SearchParams | None = None,
        filter_params: FilterParams | None = None,
    ) -> int:
        """Compile, for batches of ``batch_size``, every program a
        predicate of ``example``'s shape can be planned to
        (``core.plan.Planner.buckets``): each packed size of the exact
        path and each beam factor of the graph's; returns how many. A
        server that calls this before taking traffic compiles nothing when
        a new predicate arrives."""
        p = self.resolve_params(k, params)
        fp = filter_params if filter_params is not None else FilterParams()
        cap = fp.max_filter_oversample
        cf = filter_mod.compile_filter(example, self.schema, self.vocab)
        q = np.zeros((batch_size, self.dim), np.float32)
        groups = self.planner.buckets(p, cap, batch_size)
        slots = self.planner.slots(p, cap)
        none = np.zeros(batch_size, np.int32)
        for path, size in groups:
            if path == EXACT:
                jax.block_until_ready(self._exact_scan(
                    q, np.full((size, plan_mod.row_width(p.k)), PAD,
                               np.int32),
                    np.zeros(size, np.int32), none, none, p.k, slots))
            else:
                self.search(q, params=p, filter=[QueryPlan(
                    cf, path, size, self.planner.live)] * batch_size,
                    filter_params=fp)
        return len(groups)

    def profile(
        self,
        queries: np.ndarray,
        k: int | None = None,
        params: SearchParams | None = None,
        *,
        filter: FilterExpr | None = None,
        filter_params: FilterParams | None = None,
        save: str | None = None,
    ) -> tuple[search_mod.SearchResult, search_mod.HopProfile]:
        """``search`` with the per-hop trail captured (opt-in debug mode).

        Runs ``core.search.profile_search`` — the same hop transitions,
        traced as a separate scan program — and returns the translated
        ``SearchResult`` plus a :class:`repro.core.search.HopProfile`
        holding, per query per hop: the scheduled frontier page ids, the
        disk-IO / cache-hit deltas, the shrinking worst-of-top-k frontier
        and the adaptive stall counter. Calling this never perturbs the
        compiled fast path: ``search`` keeps its own executables and its
        results stay bit-identical whether or not profiling ever ran.

        ``save=`` writes the profile as JSON readable by
        ``python -m repro.obs.report``. Not supported over a streamed
        (memory-budgeted) index — reload without ``memory_budget``.
        """
        if self.fetcher is not None:
            raise ValueError(
                "profile() over a streamed (memory-budgeted) index is not "
                "supported: reload without memory_budget to profile"
            )
        p = self.resolve_params(k, params)
        meta = fshape = fvals = None
        if filter is not None:
            # the hop loop's trail: every query on the graph path, the beam
            # widened for the most selective of them
            fp = filter_params if filter_params is not None else FilterParams()
            plans = self._plans(filter, len(queries), p, fp)
            shapes = {pl.filter.shape for pl in plans}
            if len(shapes) != 1:
                raise ValueError("profile() takes filters of one shape")
            fshape = shapes.pop()
            factor = max(self.planner.factor(pl.count, p,
                                             fp.max_filter_oversample)
                         for pl in plans)
            p = p.replace(beam_width=p.beam_width * factor)
            meta = self.meta
            fvals = search_mod.FilterValues(
                codes=jnp.asarray(np.stack([pl.filter.codes for pl in plans])),
                bounds=jnp.asarray(np.stack([pl.filter.bounds
                                             for pl in plans])))
        res, trail = search_mod.profile_search(
            jnp.asarray(queries, jnp.float32), self.data, p,
            capacity=self.store.capacity,
            mode=self.cfg.memory_mode.value,
            meta=meta, fshape=fshape, fvals=fvals,
        )
        res = search_mod.SearchResult(
            ids=self.translate_ids(np.asarray(res.ids)),
            dists=np.asarray(res.dists),
            ios=np.asarray(res.ios),
            hops=np.asarray(res.hops),
            cache_hits=np.asarray(res.cache_hits),
        )
        trail = search_mod.HopProfile(*(np.asarray(a) for a in trail))
        if save is not None:
            import json

            from repro.obs.report import profile_to_dict

            with open(save, "w") as f:
                json.dump(profile_to_dict(res, trail), f)
        return res, trail

    # -------------------------------------------------------------- autotune
    def _measure(
        self, queries: jnp.ndarray, params: SearchParams, truth: np.ndarray
    ) -> dict:
        """One operating point: recall + timed wall clock over the batch.

        The first call per distinct ``params`` compiles (SearchParams is a
        static jit arg); timing reruns the compiled executable. p99 latency
        is estimated from the hop distribution — per-query cost is hop-
        dominated (each hop is one batched page-record read), so
        ``mean_us * p99_hops / mean_hops`` prices the straggler lanes
        without needing per-query timers inside one vmapped batch."""
        res = self._raw_search(queries, params)          # compile + warm
        jnp.asarray(res.ids).block_until_ready()
        t0 = time.perf_counter()
        res = self._raw_search(queries, params)
        jnp.asarray(res.ids).block_until_ready()
        wall = time.perf_counter() - t0
        found = self.translate_ids(np.asarray(res.ids))
        recall = recall_at_k(found[:, : truth.shape[1]], truth)
        hops = np.asarray(res.hops)
        mean_us = wall / queries.shape[0] * 1e6
        mean_hops = float(hops.mean())
        p99_scale = (
            float(np.percentile(hops, 99)) / mean_hops if mean_hops else 1.0
        )
        return dict(
            params=params,
            recall=float(recall),
            qps=queries.shape[0] / wall if wall > 0 else float("inf"),
            mean_us=mean_us,
            p99_us=mean_us * p99_scale,
            mean_hops=mean_hops,
            mean_ios=float(np.asarray(res.ios).mean()),
        )

    def autotune(
        self,
        queries: np.ndarray,
        *,
        recall_target: float | None = None,
        p99_target_us: float | None = None,
        k: int = 10,
        truth: np.ndarray | None = None,
        beam_grid: tuple | None = None,
        patience_grid: tuple = (None, 2, 4),
        io_batch_grid: tuple | None = None,
        entries_grid: tuple | None = None,
        store: bool = True,
    ) -> dict:
        """Find the cheapest operating point meeting a recall (or p99
        latency) target over THIS loaded index — no rebuilds, one compiled
        executable per probed ``SearchParams`` (cheap since PR 3).

        Recall mode: recall is monotone in beam width, so binary-search the
        beam ladder for the cheapest rung meeting ``recall_target``, then
        refine around it with the adaptive knobs (early-termination
        patience, io_batch, entry count/slack) and keep the highest-QPS
        variant still meeting the target. Latency mode
        (``p99_target_us``): highest-recall measured point within budget.

        The winner is appended to ``self.tuned`` and becomes
        ``default_params`` (``store=True``); ``save`` round-trips it
        through the manifest's ``tuned`` section so
        ``load_index(...).search(q)`` and ``--recall-target`` serving run
        it with zero per-process retuning. Returns the winning measurement
        dict (params/recall/qps/p99_us/...).
        """
        if (recall_target is None) == (p99_target_us is None):
            raise ValueError(
                "autotune needs exactly one of recall_target= or "
                "p99_target_us="
            )
        q = jnp.asarray(queries, jnp.float32)
        if truth is None:
            truth = vamana_mod.brute_force_knn(
                self.vectors_by_original_id(), np.asarray(queries), k
            )
        truth = np.asarray(truth)[:, :k]

        base = SearchParams.from_config(self.cfg, k=k)
        t = base.lsh_entries
        if beam_grid is None:
            bw = base.beam_width
            beam_grid = tuple(sorted({max(t, bw // 4), max(t, bw // 2),
                                      bw, 2 * bw}))
        beam_grid = tuple(sorted(beam_grid))
        measured: list[dict] = []

        def probe(p: SearchParams) -> dict:
            m = self._measure(q, p, truth)
            measured.append(m)
            return m

        if recall_target is not None:
            # binary search the beam ladder: cheapest rung >= target
            lo, hi = 0, len(beam_grid) - 1
            best_rung = None
            while lo <= hi:
                mid = (lo + hi) // 2
                m = probe(base.replace(beam_width=beam_grid[mid]))
                if m["recall"] >= recall_target:
                    best_rung = m
                    hi = mid - 1
                else:
                    lo = mid + 1
            if best_rung is None:       # even the widest rung missed
                best_rung = max(measured, key=lambda m: m["recall"])
            # refine at the chosen rung: adaptive + cheaper-I/O variants
            rung = best_rung["params"]
            variants: list[SearchParams] = []
            for pat in patience_grid:
                if pat is not None:
                    variants.append(rung.replace(
                        adaptive=AdaptiveParams(patience=pat)))
            for iob in (io_batch_grid or ()):
                if iob != rung.io_batch:
                    variants.append(rung.replace(io_batch=iob))
            for ent in (entries_grid or ()):
                if ent != rung.lsh_entries and ent <= rung.beam_width:
                    variants.append(rung.replace(lsh_entries=ent))
            for v in variants:
                probe(v)
            ok = [m for m in measured if m["recall"] >= recall_target]
            pool = ok or [max(measured, key=lambda m: m["recall"])]
            winner = max(pool, key=lambda m: m["qps"])
            target = {"recall": recall_target}
        else:
            for b in beam_grid:
                probe(base.replace(beam_width=b))
                for pat in patience_grid:
                    if pat is not None:
                        probe(base.replace(
                            beam_width=b,
                            adaptive=AdaptiveParams(patience=pat)))
            ok = [m for m in measured if m["p99_us"] <= p99_target_us]
            pool = ok or [min(measured, key=lambda m: m["p99_us"])]
            winner = max(pool, key=lambda m: m["recall"])
            target = {"p99_us": p99_target_us}

        winner = dict(winner, target=target)
        if store:
            self.tuned.append(winner)
            self.tuned_default = winner["params"]
        return winner

    def params_for_target(
        self,
        recall_target: float | None = None,
        p99_target_us: float | None = None,
    ) -> SearchParams:
        """Resolve a stored tuned operating point for a serving target.

        Picks among points recorded by ``autotune`` (round-tripped through
        the manifest): for a recall target, the highest-QPS point whose
        measured recall meets it; for a latency target, the highest-recall
        point within budget. Raises ``LookupError`` when nothing stored
        qualifies — serving surfaces that as "autotune this index first"."""
        if (recall_target is None) == (p99_target_us is None):
            raise ValueError(
                "need exactly one of recall_target= or p99_target_us="
            )
        if recall_target is not None:
            ok = [m for m in self.tuned if m["recall"] >= recall_target]
            if not ok:
                raise LookupError(
                    f"no tuned operating point reaches recall "
                    f"{recall_target}: run autotune(queries, recall_target="
                    f"{recall_target}) on this index and save it"
                )
            return max(ok, key=lambda m: m["qps"])["params"]
        ok = [m for m in self.tuned if m["p99_us"] <= p99_target_us]
        if not ok:
            raise LookupError(
                f"no tuned operating point meets p99 <= {p99_target_us}us: "
                f"run autotune(queries, p99_target_us={p99_target_us}) on "
                "this index and save it"
            )
        return max(ok, key=lambda m: m["recall"])["params"]

    # -------------------------------------------------------------- lifecycle
    def save(self, directory: str) -> None:
        """Persist to ``directory``: page-aligned ``pages.bin`` (the paper's
        disk layout, memmap-readable) + numpy sidecars + JSON manifest."""
        from repro.core import persist

        persist.save_pageann(self, directory)

    @classmethod
    def load(cls, directory: str, *, memory_budget=None) -> "PageANNIndex":
        """Reload a saved index; searches are bit-identical to the original.

        ``memory_budget`` (``repro.core.MemoryBudget`` | bytes | fraction |
        spec string | None) caps the device-resident page-record region;
        pages beyond it stream from the ``pages.bin`` memmap per hop with
        no change to search results. ``None`` = fully resident."""
        from repro.core import persist

        return persist.load_pageann(directory, memory_budget=memory_budget)


def recall_at_k(found_ids: np.ndarray, truth_ids: np.ndarray) -> float:
    """Mean recall@k over a query batch (paper's Recall@10 metric).

    Set semantics per row (duplicates counted once on both sides, PAD ids
    included verbatim — identical to the former per-query
    ``len(set & set)`` loop), vectorized as one broadcast comparison:
    a truth entry scores iff it appears anywhere in the found row and is
    the first occurrence of its value within the truth row.
    """
    found = np.asarray(found_ids)
    truth = np.asarray(truth_ids)
    q, k = truth.shape
    present = (truth[:, :, None] == found[:, None, :]).any(-1)     # (Q, k)
    j = np.arange(k)
    dup = ((truth[:, :, None] == truth[:, None, :])
           & (j[None, None, :] < j[None, :, None])).any(-1)        # (Q, k)
    return float((present & ~dup).sum() / (q * k))


def _device_bytes() -> int:
    """Memory of the default device as its backend reports it, else a TPU
    v5e's 16 GiB (the CPU backend reports none)."""
    try:
        stats = jax.devices()[0].memory_stats() or {}
    except Exception:       # a backend without memory statistics
        stats = {}
    return int(stats.get("bytes_limit", plan_mod.DEFAULT_DEVICE_BYTES))

