"""Baselines the paper compares against, re-implemented in JAX.

* ``diskann_search`` — DiskANN-style traversal: a vector-granularity Vamana
  beam search where next hops are chosen with in-memory PQ estimates and every
  expanded node costs one disk read of its (vector + adjacency) record. With
  id-ordered placement multiple unrelated vectors share an SSD page, so each
  node read drags a full page: the read-amplification regime of Table 1.

* ``starling_search`` — Starling-style variant: identical traversal but the
  disk layout packs *similar* vectors per page (we reuse PageANN's grouping)
  and a page, once read, contributes all its members to reranking, so repeat
  visits to co-located vectors are free (unique-page accounting).

Both count "Mean I/Os" the same way the paper's Table 3 does, which makes
them directly comparable with ``core.search`` on the same data.

:class:`DiskANNIndex` / :class:`StarlingIndex` wrap the raw searches in the
same :class:`repro.core.protocol.VectorIndex` lifecycle as
``PageANNIndex`` — build/from_data → save → load → ``search(queries, k,
params)`` returning a ``SearchResult`` — so tests and the serving
engine drive all three systems through one code path.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pq as pq_mod
from repro.core.config import PageANNConfig, SearchParams, resolve_search_params

PAD = -1
INF = jnp.inf


class BaselineData(NamedTuple):
    x: jnp.ndarray           # (N, d) full vectors ('on disk')
    nbrs: jnp.ndarray        # (N, R) vamana adjacency ('on disk' with vector)
    codes: jnp.ndarray       # (N, M) PQ codes (in memory — DiskANN keeps these)
    codebooks: jnp.ndarray   # (M, ksub, dsub)
    page_of: jnp.ndarray     # (N,) page id of each vector under the layout
    entry: jnp.ndarray       # () medoid id


class BaselineResult(NamedTuple):
    ids: jnp.ndarray
    dists: jnp.ndarray
    ios: jnp.ndarray       # page reads
    hops: jnp.ndarray


def _beam_search_one(
    q, data: BaselineData, *, beam, k, max_hops, io_batch, unique_pages: bool
):
    n, r = data.nbrs.shape
    num_pages = jnp.max(data.page_of) + 1

    lut = pq_mod.pq_lut(q, data.codebooks)

    cand_ids = jnp.full((beam,), PAD, jnp.int32).at[0].set(data.entry)
    cand_d = jnp.full((beam,), INF, jnp.float32).at[0].set(
        pq_mod.adc_distance(data.codes[data.entry][None], lut)[0]
    )
    cand_vis = jnp.zeros((beam,), bool)
    node_vis = jnp.zeros((n,), bool)
    # visited-page bitmap: only consulted when unique_pages (Starling layout)
    page_vis = jnp.zeros((data.page_of.shape[0],), bool)  # sized N >= P
    res_ids = jnp.full((k,), PAD, jnp.int32)
    res_d = jnp.full((k,), INF, jnp.float32)
    io = jnp.int32(0)
    hops = jnp.int32(0)

    def cond(s):
        cand_ids, cand_d, cand_vis, node_vis, page_vis, res_ids, res_d, io, hops = s
        live = (~cand_vis) & (cand_ids != PAD) & jnp.isfinite(cand_d)
        return live.any() & (hops < max_hops)

    def body(s):
        cand_ids, cand_d, cand_vis, node_vis, page_vis, res_ids, res_d, io, hops = s

        batch = jnp.full((io_batch,), PAD, jnp.int32)

        def pick(j, carry):
            cand_vis, node_vis, batch = carry
            masked = jnp.where(cand_vis | (cand_ids == PAD), INF, cand_d)
            slot = jnp.argmin(masked)
            ok = jnp.isfinite(masked[slot])
            cand_vis = cand_vis.at[slot].set(True)
            v = jnp.where(ok, cand_ids[slot], PAD)
            node_vis = jnp.where(
                ok, node_vis.at[jnp.maximum(v, 0)].set(True), node_vis
            )
            return cand_vis, node_vis, batch.at[j].set(v)

        cand_vis, node_vis, batch = jax.lax.fori_loop(
            0, io_batch, pick, (cand_vis, node_vis, batch)
        )
        ok = batch >= 0
        safe = jnp.maximum(batch, 0)

        # --- the disk read: vector + adjacency record of each expanded node
        pages = data.page_of[safe]
        if unique_pages:
            fresh = ok & ~page_vis[pages]
            # two batch entries may share a page: count once
            first = jnp.zeros_like(fresh)
            seen = jnp.full((io_batch,), PAD, jnp.int32)

            def dedup(j, carry):
                first, seen = carry
                dup = (seen == pages[j]).any()
                first = first.at[j].set(fresh[j] & ~dup)
                seen = seen.at[j].set(jnp.where(ok[j], pages[j], PAD))
                return first, seen

            first, _ = jax.lax.fori_loop(0, io_batch, dedup, (first, seen))
            io2 = io + first.sum().astype(jnp.int32)
            page_vis = page_vis.at[jnp.where(ok, pages, 0)].set(
                page_vis[jnp.where(ok, pages, 0)] | ok
            )
        else:
            io2 = io + ok.sum().astype(jnp.int32)  # one page read per node

        vec = data.x[safe]                      # (b, d)
        adj = data.nbrs[safe]                   # (b, R)

        # exact rerank of the expanded nodes
        ex = jnp.sum((vec - q[None, :]) ** 2, axis=-1)
        ex = jnp.where(ok, ex, INF)
        all_rd = jnp.concatenate([res_d, ex])
        all_ri = jnp.concatenate([res_ids, batch])
        order = jnp.argsort(all_rd)[:k]
        res_d2, res_ids2 = all_rd[order], all_ri[order]

        # estimated distances of neighbors via in-memory PQ
        flat = adj.reshape(-1)
        validn = (flat != PAD) & ok.repeat(r)
        est = pq_mod.adc_distance(data.codes[jnp.maximum(flat, 0)], lut)
        est = jnp.where(validn, est, INF)
        est = jnp.where(node_vis[jnp.maximum(flat, 0)], INF, est)
        dup = (flat[:, None] == cand_ids[None, :]).any(1)
        est = jnp.where(dup, INF, est)
        o = jnp.argsort(flat)
        sflat = flat[o]
        dupm = jnp.concatenate([jnp.array([False]), sflat[1:] == sflat[:-1]])
        dup2 = jnp.zeros_like(dupm).at[o].set(dupm)
        est = jnp.where(dup2 & (flat != PAD), INF, est)

        all_ci = jnp.concatenate([cand_ids, flat])
        all_cd = jnp.concatenate([cand_d, est])
        all_cv = jnp.concatenate([cand_vis, jnp.zeros_like(validn)])
        order = jnp.argsort(all_cd)[:beam]
        return (
            all_ci[order], all_cd[order], all_cv[order],
            node_vis, page_vis, res_ids2, res_d2, io2, hops + 1,
        )

    s = (cand_ids, cand_d, cand_vis, node_vis, page_vis, res_ids, res_d, io, hops)
    s = jax.lax.while_loop(cond, body, s)
    return s[5], s[6], s[7], s[8]


@functools.partial(
    jax.jit,
    static_argnames=("beam", "k", "max_hops", "io_batch", "unique_pages"),
)
def baseline_search(
    queries, data: BaselineData, *, beam, k, max_hops, io_batch, unique_pages
) -> BaselineResult:
    fn = functools.partial(
        _beam_search_one,
        data=data,
        beam=beam,
        k=k,
        max_hops=max_hops,
        io_batch=io_batch,
        unique_pages=unique_pages,
    )
    ids, dists, ios, hops = jax.vmap(fn)(queries)
    return BaselineResult(ids=ids, dists=dists, ios=ios, hops=hops)


def make_baseline_data(
    x: np.ndarray,
    nbrs: np.ndarray,
    codebooks: np.ndarray,
    page_of: np.ndarray | None = None,
    vectors_per_page: int | None = None,
) -> BaselineData:
    """id-order layout when page_of is None (DiskANN); else custom layout."""
    from repro.core.vamana import medoid

    x = np.asarray(x, np.float32)
    codes = np.asarray(
        pq_mod.pq_encode(jnp.asarray(x), jnp.asarray(codebooks))
    )
    if page_of is None:
        vpp = vectors_per_page or max(1, 4096 // (x.shape[1] * 4))
        page_of = np.arange(len(x)) // vpp
    return BaselineData(
        x=jnp.asarray(x),
        nbrs=jnp.asarray(nbrs),
        codes=jnp.asarray(codes),
        codebooks=jnp.asarray(codebooks),
        page_of=jnp.asarray(page_of.astype(np.int32)),
        entry=jnp.asarray(medoid(x), jnp.int32),
    )


def diskann_search(queries, data: BaselineData, *, beam=64, k=10, max_hops=64, io_batch=5):
    return baseline_search(
        queries, data, beam=beam, k=k, max_hops=max_hops,
        io_batch=io_batch, unique_pages=False,
    )


def starling_search(queries, data: BaselineData, *, beam=64, k=10, max_hops=64, io_batch=5):
    return baseline_search(
        queries, data, beam=beam, k=k, max_hops=max_hops,
        io_batch=io_batch, unique_pages=True,
    )


# --------------------------------------------------------------------------
# VectorIndex lifecycle wrappers (protocol shared with PageANNIndex)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class BaselineStats:
    num_vectors: int
    pages: int
    memory_bytes: int   # in-memory PQ codes + codebooks (what DiskANN keeps)


class _BaselineIndex:
    """Shared ``VectorIndex`` plumbing over a :class:`BaselineData`.

    Ids are never reassigned by the baselines, so ``search`` results are
    already ORIGINAL vector ids; ``cache_hits`` is always zero (no warmed
    page cache in either baseline).
    """

    kind: str = ""
    _unique_pages: bool = False

    def __init__(self, data: BaselineData):
        self.data = data

    # ------------------------------------------------------------ properties
    @property
    def dim(self) -> int:
        return int(self.data.x.shape[1])

    @property
    def default_params(self) -> SearchParams:
        return SearchParams()

    def resolve_params(
        self, k: int | None, params: SearchParams | None
    ) -> SearchParams:
        return resolve_search_params(self.default_params, k, params)

    @property
    def stats(self) -> BaselineStats:
        return BaselineStats(
            num_vectors=int(self.data.x.shape[0]),
            pages=int(np.asarray(self.data.page_of).max()) + 1,
            memory_bytes=int(
                self.data.codes.size + self.data.codebooks.size * 4
            ),
        )

    # ----------------------------------------------------------------- search
    def search(
        self,
        queries: np.ndarray,
        k: int | None = None,
        params: SearchParams | None = None,
    ):
        from repro.core.search import SearchResult

        p = self.resolve_params(k, params)
        res = baseline_search(
            jnp.asarray(queries, jnp.float32),
            self.data,
            beam=p.beam_width,
            k=p.k,
            max_hops=p.max_hops,
            io_batch=p.io_batch,
            unique_pages=self._unique_pages,
        )
        ios = np.asarray(res.ios)
        return SearchResult(
            ids=np.asarray(res.ids),
            dists=np.asarray(res.dists),
            ios=ios,
            hops=np.asarray(res.hops),
            cache_hits=np.zeros_like(ios),
        )

    # -------------------------------------------------------------- lifecycle
    def save(self, directory: str) -> None:
        from repro.core import persist

        os.makedirs(directory, exist_ok=True)
        np.savez(
            os.path.join(directory, persist.ARRAYS_NPZ),
            x=np.asarray(self.data.x),
            nbrs=np.asarray(self.data.nbrs),
            codes=np.asarray(self.data.codes),
            codebooks=np.asarray(self.data.codebooks),
            page_of=np.asarray(self.data.page_of),
            entry=np.asarray(self.data.entry),
        )
        persist.write_manifest(
            directory,
            dict(kind=self.kind, dim=self.dim,
                 stats=dataclasses.asdict(self.stats)),
        )

    @classmethod
    def load(cls, directory: str) -> "_BaselineIndex":
        from repro.core import persist

        doc = persist.read_manifest(directory)
        if doc["kind"] != cls.kind:
            raise ValueError(
                f"{directory}: kind={doc['kind']!r}, expected {cls.kind!r}"
            )
        with np.load(os.path.join(directory, persist.ARRAYS_NPZ)) as z:
            data = BaselineData(
                x=jnp.asarray(z["x"]),
                nbrs=jnp.asarray(z["nbrs"]),
                codes=jnp.asarray(z["codes"]),
                codebooks=jnp.asarray(z["codebooks"]),
                page_of=jnp.asarray(z["page_of"]),
                entry=jnp.asarray(z["entry"]),
            )
        return cls(data)

    # --------------------------------------------------------------- builders
    @classmethod
    def from_data(
        cls,
        x: np.ndarray,
        nbrs: np.ndarray,
        codebooks: np.ndarray,
        *,
        page_of: np.ndarray | None = None,
        vectors_per_page: int | None = None,
    ) -> "_BaselineIndex":
        """Wrap a prebuilt Vamana graph + PQ codebooks (shared with PageANN
        sweeps so all systems search the same graph)."""
        return cls(
            make_baseline_data(
                np.asarray(x), np.asarray(nbrs), np.asarray(codebooks),
                page_of=page_of, vectors_per_page=vectors_per_page,
            )
        )

    @classmethod
    def build(cls, x: np.ndarray, cfg: PageANNConfig) -> "_BaselineIndex":
        """Full build from raw vectors using the config's graph/PQ knobs."""
        from repro.core.vamana import build_vamana

        x = np.ascontiguousarray(x, np.float32)
        nbrs = build_vamana(
            x, degree=cfg.graph_degree, beam=cfg.build_beam,
            alpha=cfg.alpha, rounds=cfg.build_rounds, seed=cfg.seed,
        )
        books = np.asarray(pq_mod.train_pq(
            x, cfg.pq_subspaces, cfg.pq_ksub, cfg.pq_iters, seed=cfg.seed
        ))
        return cls.from_data(x, nbrs, books, page_of=cls._layout(x, nbrs, cfg))

    @classmethod
    def _layout(cls, x, nbrs, cfg: PageANNConfig):
        return None  # id-order pages (DiskANN); Starling overrides


class DiskANNIndex(_BaselineIndex):
    kind = "diskann"
    _unique_pages = False


class StarlingIndex(_BaselineIndex):
    kind = "starling"
    _unique_pages = True

    @classmethod
    def _layout(cls, x, nbrs, cfg: PageANNConfig):
        from repro.core.page_graph import group_pages

        return group_pages(x, nbrs, cfg.resolve_capacity(), cfg.hop_h).page_of


BASELINE_KINDS = {
    DiskANNIndex.kind: DiskANNIndex,
    StarlingIndex.kind: StarlingIndex,
}


def load_baseline(directory: str) -> _BaselineIndex:
    from repro.core import persist

    kind = persist.read_manifest(directory)["kind"]
    return BASELINE_KINDS[kind].load(directory)
