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

import jax.numpy as jnp
import numpy as np

from repro.core import filter as filter_mod
from repro.core import layout as layout_mod
from repro.core import lsh as lsh_mod
from repro.core import page_graph as pg_mod
from repro.core import pq as pq_mod
from repro.core import search as search_mod
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
    # the original-order host copy (selectivity probe / brute-force
    # oracle / compaction source). All None/empty without a schema.
    schema: MetadataSchema | None = None
    vocab: dict = dataclasses.field(default_factory=dict)
    meta: MetaArrays | None = None
    meta_host: MetaArrays | None = None
    # per-FilterExpr compiled form + measured selectivity (host cache —
    # compiling and probing once per distinct predicate, like the jit
    # executable the static arg keys)
    _filter_cache: dict = dataclasses.field(default_factory=dict, repr=False)

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
        meta=None, cfilter=None,
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
                meta=meta, cfilter=cfilter,
            )
        if self.fetcher is not None:
            return search_mod.stream_search(
                q, self.data, params,
                capacity=self.store.capacity,
                mode=self.cfg.memory_mode.value,
                fetcher=self.fetcher,
                meta=meta, cfilter=cfilter,
            )
        return search_mod.batch_search(
            q, self.data, params,
            capacity=self.store.capacity,
            mode=self.cfg.memory_mode.value,
            meta=meta, cfilter=cfilter,
        )

    # ----------------------------------------------------------------- filter
    def compiled_filter(self, expr: FilterExpr):
        """Resolve a ``FilterExpr`` against this index's schema/vocab and
        measure its selectivity (fraction of live vectors passing) over
        the host metadata columns. Cached per expression — the compiled
        form keys one jit executable, the selectivity drives the beam
        oversampling. Returns (CompiledFilter, selectivity)."""
        cached = self._filter_cache.get(expr)
        if cached is not None:
            return cached
        cf = filter_mod.compile_filter(expr, self.schema, self.vocab)
        mask = filter_mod.filter_mask_np(
            cf, self.meta_host.tags, self.meta_host.nums
        )
        sel = float(mask.mean()) if mask.size else 0.0
        self._filter_cache[expr] = (cf, sel)
        return cf, sel

    @staticmethod
    def _filter_oversample(selectivity: float, cap: int) -> int:
        """Pow2 beam-widening factor for a predicate's selectivity: a
        filter passing 1/s of the corpus needs ~s× the frontier to
        surface as many passing candidates as the unfiltered search —
        bucketed to powers of two (bounded compiled shapes, like the
        tombstone oversampling) and clamped to ``cap``."""
        if selectivity <= 0.0:
            return cap
        need = 1.0 / selectivity
        b = 1
        while b < need and b < cap:
            b *= 2
        return min(b, cap)

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
        ``fetch_wall_s``); zeros when fully resident."""
        if self.fetcher is None:
            return dict(pages_fetched=0, fetch_hits=0, fetch_wall_s=0.0)
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
        the predicate (see ``core.filter``): the compiled filter masks
        non-passing members to ``+inf`` inside the page scan, and the
        beam is widened by a pow2 factor of the predicate's measured
        selectivity (bounded by
        ``filter_params.max_filter_oversample``) so recall matches a
        post-filter brute force. ``filter=None`` compiles and runs the
        exact pre-filter program.
        """
        p = self.resolve_params(k, params)
        meta = cfilter = None
        if filter is not None:
            fp = filter_params if filter_params is not None else FilterParams()
            cfilter, sel = self.compiled_filter(filter)
            factor = self._filter_oversample(sel, fp.max_filter_oversample)
            if factor > 1:
                p = p.replace(beam_width=p.beam_width * factor)
            meta = self.meta
        res = self._raw_search(
            jnp.asarray(queries, jnp.float32), p, mesh=mesh,
            meta=meta, cfilter=cfilter,
        )
        return search_mod.SearchResult(
            ids=self.translate_ids(res.ids),
            dists=np.asarray(res.dists),
            ios=np.asarray(res.ios),
            hops=np.asarray(res.hops),
            cache_hits=np.asarray(res.cache_hits),
            shared_reads=np.asarray(res.shared_reads),
        )

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
        meta = cfilter = None
        if filter is not None:
            fp = filter_params if filter_params is not None else FilterParams()
            cfilter, sel = self.compiled_filter(filter)
            factor = self._filter_oversample(sel, fp.max_filter_oversample)
            if factor > 1:
                p = p.replace(beam_width=p.beam_width * factor)
            meta = self.meta
        res, trail = search_mod.profile_search(
            jnp.asarray(queries, jnp.float32), self.data, p,
            capacity=self.store.capacity,
            mode=self.cfg.memory_mode.value,
            meta=meta, cfilter=cfilter,
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
