"""Out-of-HBM streaming page tier: MemoryBudget loads, bit-identity vs
fully resident search, the host fetcher, and the serving-surface plumbing
(stats split, engine metrics, database loads)."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    MemoryBudget,
    MemoryMode,
    MutableIndex,
    PageANNConfig,
    PageANNIndex,
    SearchParams,
    load_index,
)
from repro.core import baselines as bl
from repro.core import persist
from repro.core import search as search_mod
from repro.core import stream as stream_mod
from repro.core.vamana import brute_force_knn
from repro.data.pipeline import clustered_vectors, query_vectors

N, D, Q = 1200, 32, 12


@pytest.fixture(scope="module")
def dataset():
    x = clustered_vectors(N, D, num_clusters=16, seed=0)
    q = query_vectors(x, Q, seed=1)
    truth = brute_force_knn(x, q, 10)
    return x, q, truth


def _cfg(**kw):
    base = dict(
        dim=D, graph_degree=12, build_beam=24, pq_subspaces=8,
        lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
        memory_mode=MemoryMode.HYBRID,
    )
    base.update(kw)
    return PageANNConfig(**base)


@pytest.fixture(scope="module", params=list(MemoryMode), ids=lambda m: m.value)
def mode_artifact(request, dataset, tmp_path_factory):
    """One saved artifact per MemoryMode, warmed so page_order carries
    real access counts — what a budgeted load pins its residents by."""
    x, q, _ = dataset
    idx = PageANNIndex.build(x, _cfg(memory_mode=request.param))
    idx.warm_cache(np.asarray(q), params=SearchParams.from_config(idx.cfg))
    art = str(tmp_path_factory.mktemp("stream") / f"idx.{request.param.value}")
    idx.save(art)
    return art


@pytest.fixture(scope="module")
def hybrid_artifact(dataset, tmp_path_factory):
    x, q, _ = dataset
    idx = PageANNIndex.build(x, _cfg())
    idx.warm_cache(np.asarray(q), params=SearchParams.from_config(idx.cfg))
    art = str(tmp_path_factory.mktemp("stream_hy") / "idx.pageann")
    idx.save(art)
    return art


# ----------------------------------------------------------- MemoryBudget
def test_memory_budget_validation():
    with pytest.raises(ValueError, match="exactly one"):
        MemoryBudget()
    with pytest.raises(ValueError, match="exactly one"):
        MemoryBudget(bytes=1 << 20, fraction=0.5)
    with pytest.raises(ValueError, match="positive"):
        MemoryBudget(bytes=0)
    with pytest.raises(ValueError):
        MemoryBudget(bytes=2.5)
    for bad in (0.0, -0.25, 1.5):
        with pytest.raises(ValueError, match="fraction"):
            MemoryBudget(fraction=bad)
    # frozen + hashable: usable as a static jit closure component
    assert hash(MemoryBudget(fraction=0.5)) == hash(MemoryBudget(fraction=0.5))


def test_memory_budget_parse():
    assert MemoryBudget.parse("512MB") == MemoryBudget(bytes=512 * 10**6)
    assert MemoryBudget.parse("1GiB") == MemoryBudget(bytes=1 << 30)
    assert MemoryBudget.parse("0.25") == MemoryBudget(fraction=0.25)
    assert MemoryBudget.parse(0.25) == MemoryBudget(fraction=0.25)
    assert MemoryBudget.parse(4096) == MemoryBudget(bytes=4096)
    b = MemoryBudget(fraction=0.5)
    assert MemoryBudget.parse(b) is b
    with pytest.raises(ValueError):
        MemoryBudget.parse(True)
    with pytest.raises(ValueError):
        MemoryBudget.parse("lots")


def test_memory_budget_resolve_pages():
    assert MemoryBudget(fraction=0.25).resolve_pages(40, 4096) == 10
    assert MemoryBudget(fraction=1.0).resolve_pages(40, 4096) == 40
    # floors, clamps to [1, num_pages]
    assert MemoryBudget(fraction=0.26).resolve_pages(40, 4096) == 10
    assert MemoryBudget(fraction=0.001).resolve_pages(40, 4096) == 1
    assert MemoryBudget(bytes=3 * 4096).resolve_pages(40, 4096) == 3
    assert MemoryBudget(bytes=10**12).resolve_pages(40, 4096) == 40


def test_memory_budget_json_round_trip():
    for b in (MemoryBudget(fraction=0.25), MemoryBudget(bytes=1 << 20)):
        assert MemoryBudget.from_json(json.loads(json.dumps(b.to_json()))) == b


# ----------------------------------------------- bit-identity vs resident
def test_streamed_search_bit_identical_every_mode(dataset, mode_artifact):
    """The tentpole acceptance bar: a load under a 0.25x budget (~4x more
    pages on disk than resident) returns bit-identical
    ids/dists/ios/hops/cache_hits on every MemoryMode."""
    _, q, _ = dataset
    full = PageANNIndex.load(mode_artifact)
    streamed = PageANNIndex.load(
        mode_artifact, memory_budget=MemoryBudget(fraction=0.25)
    )
    assert streamed.fetcher is not None
    assert streamed.stats.resident_pages * 4 <= streamed.stats.pages

    want = full.search(q, k=10)
    got = streamed.search(q, k=10)
    for field in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, field)),
            np.asarray(getattr(got, field)),
            err_msg=field,
        )
    fs = streamed.fetch_stats()
    assert fs["pages_fetched"] > 0          # the streaming path really ran
    assert full.fetch_stats() == dict(
        pages_fetched=0, fetch_hits=0, fetch_wall_s=0.0, fetch_calls=0,
        staged_slots=0,
    )


def test_byte_budget_pins_exact_page_count(dataset, hybrid_artifact):
    _, q, _ = dataset
    with open(os.path.join(hybrid_artifact, "manifest.json")) as f:
        rec_bytes = json.load(f)["page_record_bytes"]
    idx = PageANNIndex.load(
        hybrid_artifact, memory_budget=MemoryBudget(bytes=5 * rec_bytes)
    )
    assert idx.stats.resident_pages == 5
    assert idx.stats.resident_bytes == 5 * rec_bytes
    full = PageANNIndex.load(hybrid_artifact)
    np.testing.assert_array_equal(
        idx.search(q, k=10).ids, full.search(q, k=10).ids
    )


def test_budget_covering_whole_file_is_fully_resident(dataset, hybrid_artifact):
    """A budget that fits every page degenerates to the plain resident
    load: no fetcher, no streaming executable, identical stats."""
    idx = PageANNIndex.load(
        hybrid_artifact, memory_budget=MemoryBudget(fraction=1.0)
    )
    assert idx.fetcher is None
    assert idx.stats.resident_pages == idx.stats.pages
    assert idx.stats.resident_bytes == idx.stats.disk_bytes


# ----------------------------------------------------- stats + manifest
def test_stats_report_resident_streamed_split(hybrid_artifact):
    streamed = PageANNIndex.load(hybrid_artifact, memory_budget=0.25)
    s = streamed.stats
    assert 0 < s.resident_pages < s.pages
    assert 0 < s.resident_bytes < s.disk_bytes
    assert s.resident_bytes == s.resident_pages * streamed.store.padded_tile_bytes()


def test_budget_round_trips_through_manifest(tmp_path, dataset, hybrid_artifact):
    """Re-saving a budgeted index writes the FULL page file (the memmap is
    the source of truth) and records the budget in the manifest's
    residency section; the re-saved artifact reloads at full residency."""
    _, q, _ = dataset
    budget = MemoryBudget(fraction=0.25)
    streamed = PageANNIndex.load(hybrid_artifact, memory_budget=budget)
    art2 = str(tmp_path / "resaved.pageann")
    streamed.save(art2)

    with open(os.path.join(art2, "manifest.json")) as f:
        doc = json.load(f)
    res = doc["residency"]
    assert MemoryBudget.from_json(res["memory_budget"]) == budget
    assert res["resident_pages"] == streamed.stats.resident_pages
    assert res["total_pages"] == streamed.stats.pages
    assert (
        os.path.getsize(os.path.join(art2, "pages.bin"))
        == os.path.getsize(os.path.join(hybrid_artifact, "pages.bin"))
    )
    full = PageANNIndex.load(art2)
    assert full.fetcher is None
    np.testing.assert_array_equal(
        full.search(q, k=10).ids,
        PageANNIndex.load(hybrid_artifact).search(q, k=10).ids,
    )


def test_unbudgeted_manifest_has_null_budget(hybrid_artifact):
    with open(os.path.join(hybrid_artifact, "manifest.json")) as f:
        doc = json.load(f)
    assert doc["residency"]["memory_budget"] is None
    assert (
        doc["residency"]["resident_pages"] == doc["residency"]["total_pages"]
    )


# ------------------------------------------------------------ PageFetcher
def test_fetcher_pad_and_shapes():
    recs = np.arange(4 * 2 * 8, dtype=np.float32).reshape(4, 2, 8)
    f = stream_mod.PageFetcher(recs)
    out = f(np.array([[2, stream_mod.PAD], [0, 3]]))
    assert out.shape == (2, 2, 2, 8)
    np.testing.assert_array_equal(out[0, 0], recs[2])
    np.testing.assert_array_equal(out[0, 1], np.zeros((2, 8), np.float32))
    np.testing.assert_array_equal(out[1, 0], recs[0])
    with pytest.raises(ValueError, match="rows"):
        stream_mod.PageFetcher(np.zeros((4, 8), np.float32))
    with pytest.raises(ValueError, match="stage_pages"):
        stream_mod.PageFetcher(recs, stage_pages=0)


def test_fetcher_lru_eviction_stays_correct():
    """A pathologically tiny staging cache (1 page) changes only the
    hit/miss split, never the returned records."""
    rng = np.random.default_rng(0)
    recs = rng.standard_normal((6, 2, 8)).astype(np.float32)
    f = stream_mod.PageFetcher(recs, stage_pages=1)
    ids = rng.integers(0, 6, size=64)
    for pid in ids:
        np.testing.assert_array_equal(f(np.array([pid]))[0], recs[pid])
    fs = f.fetch_stats()
    assert fs["pages_fetched"] + fs["fetch_hits"] == len(ids)
    assert fs["pages_fetched"] >= 6                   # capacity-1 thrashing
    f.reset_stats()
    assert f.fetch_stats() == dict(
        pages_fetched=0, fetch_hits=0, fetch_wall_s=0.0, fetch_calls=0,
        staged_slots=0, wall_window=()
    )


def test_fetcher_counters_accumulate():
    recs = np.zeros((3, 2, 8), np.float32)
    f = stream_mod.PageFetcher(recs)
    f(np.array([0, 1]))
    f(np.array([0, 1, 2]))
    fs = f.fetch_stats()
    assert fs["pages_fetched"] == 3
    assert fs["fetch_hits"] == 2
    assert fs["fetch_wall_s"] >= 0.0


def test_fetcher_fills_a_packed_request_in_flat_order():
    """Live ids at the front, PAD behind: the map's rows in the same
    order, repeats included, and zeros behind them."""
    rng = np.random.default_rng(1)
    recs = rng.standard_normal((9, 3, 8)).astype(np.float32)
    f = stream_mod.PageFetcher(recs, stage_pages=4)
    for live in ([4, 1, 7, 1, 0, 8], [8, 8], [2, 5, 3, 6, 0, 1, 4, 7], []):
        ids = np.array(live + [stream_mod.PAD] * 3)
        out = f(ids)
        assert out.shape == (len(ids), 3, 8)
        np.testing.assert_array_equal(out[:len(live)], recs[live])
        assert not out[len(live):].any()


def test_fetcher_counts_a_repeated_page_one_miss_then_hits():
    recs = np.zeros((5, 2, 8), np.float32)
    f = stream_mod.PageFetcher(recs, stage_pages=1)
    f(np.array([3, 3, stream_mod.PAD, 3, 2]))
    fs = f.fetch_stats()
    # every live slot is one hit or one miss; each distinct page misses once
    assert (fs["pages_fetched"], fs["fetch_hits"]) == (2, 2)
    assert (fs["fetch_calls"], fs["staged_slots"]) == (1, 5)


@pytest.mark.parametrize("stage_pages, calls, misses", [
    # [0, 1, 0] uses 0 last, so the 2-page cache evicts 1 for 2
    (2, [[0, 1, 0], [2], [0], [1]], [2, 1, 0, 1]),
    # a page touched by a hit is younger than one staged before it
    (2, [[0], [1], [0], [2], [0], [1]], [1, 1, 0, 1, 0, 1]),
    # one page: only the call's last page stays, so alternation thrashes
    (1, [[0, 1, 0], [0], [1], [0], [1, 1]], [2, 0, 1, 1, 1]),
    # more distinct pages in a call than the cache holds: the last ones stay
    (2, [[0, 1, 2, 3], [3], [2], [0]], [4, 0, 0, 1]),
])
def test_fetcher_evicts_the_least_recently_used(stage_pages, calls, misses):
    rng = np.random.default_rng(2)
    recs = rng.standard_normal((4, 2, 8)).astype(np.float32)
    f = stream_mod.PageFetcher(recs, stage_pages=stage_pages)
    got = []
    for ids in calls:
        before = f.fetch_stats()["pages_fetched"]
        np.testing.assert_array_equal(f(np.array(ids)), recs[ids])
        got.append(f.fetch_stats()["pages_fetched"] - before)
    assert got == misses
    fs = f.fetch_stats()
    assert fs["pages_fetched"] + fs["fetch_hits"] == sum(map(len, calls))


class SpyFetcher(stream_mod.PageFetcher):
    """A fetcher that keeps the shape and the live count of each call."""

    def __init__(self, recs, **kw):
        super().__init__(recs, **kw)
        self.calls = []

    def __call__(self, ids):
        ids = np.asarray(ids)
        self.calls.append((ids.shape, int((ids >= 0).sum())))
        return super().__call__(ids)


@pytest.mark.parametrize("lanes, b", [(8, 5), (3, 2), (1, 5)])
def test_batch_fetch_ships_the_smallest_rung_that_holds_the_hop(lanes, b):
    """Under vmap the hook packs a hop's live ids into the smallest rung of
    the ladder that holds them, asks the host for that buffer alone, and
    gives each lane its records back; a hop with no live id asks
    nothing."""
    import jax

    rng = np.random.default_rng(3)
    recs = rng.standard_normal((64, 3, 8)).astype(np.float32)
    spy = SpyFetcher(recs)
    fetch = jax.jit(jax.vmap(search_mod._fetch_hook(spy)))
    rungs = search_mod.fetch_rungs(lanes * b)
    assert rungs[0] == 0 and rungs[-1] >= lanes * b
    assert all(r % 8 == 0 for r in rungs)
    shipped = []
    for live in sorted({0, 1, *rungs[1:-1], lanes * b}):
        live = min(live, lanes * b)
        missing = np.full(lanes * b, stream_mod.PAD)
        missing[rng.choice(lanes * b, live, replace=False)] = rng.integers(
            0, 64, live)
        missing = missing.reshape(lanes, b)
        spy.calls.clear()
        got = np.asarray(fetch(missing))
        want = np.where((missing >= 0)[..., None, None],
                        recs[np.maximum(missing, 0)], 0.0)
        np.testing.assert_array_equal(got, want)
        size = min(r for r in rungs if r >= live)
        assert spy.calls == ([] if live == 0 else [((size,), live)])
        shipped += [size] if live else []
    fs = spy.fetch_stats()
    assert fs["fetch_calls"] == len(shipped)
    assert fs["staged_slots"] == sum(shipped)


def test_fetch_rungs_follow_the_batch():
    assert search_mod.fetch_rungs(64 * 5) == (0, 40, 80, 160, 320)
    assert search_mod.fetch_rungs(5) == (0, 8)
    assert search_mod.fetch_rungs(12 * 5) == (0, 8, 16, 32, 64)


@pytest.mark.parametrize("fraction, lanes, every_rung, skips", [
    (0.5, 12, True, True),    # every rung, and a hop with no live slot
    (0.25, 4, True, False),
    (0.9, 1, True, True),     # most hops find their pages resident
])
def test_streamed_search_ships_packed_buffers(dataset, hybrid_artifact,
                                             fraction, lanes, every_rung,
                                             skips):
    """The streamed search through a spy fetcher: bit for bit the resident
    answers, every buffer a rung of the ladder that its live ids fill past
    half (or the smallest rung), no call for a hop whose pages are all
    resident, and the counters the sum of the calls."""
    _, q, _ = dataset
    q = q[:lanes]
    full = PageANNIndex.load(hybrid_artifact)
    streamed = PageANNIndex.load(hybrid_artifact,
                                 memory_budget=MemoryBudget(fraction=fraction))
    params = streamed.default_params
    kw = dict(capacity=full.store.capacity, mode=full.cfg.memory_mode.value)
    spy = SpyFetcher(streamed.fetcher._recs)
    got = search_mod.stream_search(jnp.asarray(q), streamed.data, params,
                                   fetcher=spy, **kw)
    want = search_mod.batch_search(jnp.asarray(q), full.data, params, **kw)
    for field in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(want, field)),
                                      np.asarray(getattr(got, field)),
                                      err_msg=field)
    rungs = search_mod.fetch_rungs(lanes * params.io_batch)
    sizes = [shape[0] for shape, _ in spy.calls]
    assert (set(sizes) == set(rungs[1:])) is every_rung
    for (size,), live in spy.calls:
        assert 0 < live <= size and (live > size // 2 or size == rungs[1])
    # the loop ran max(hops) times; a hop whose pages were all resident
    # called nothing
    assert (len(spy.calls) < int(np.max(got.hops))) is skips
    fs = spy.fetch_stats()
    assert fs["fetch_calls"] == len(spy.calls)
    assert fs["staged_slots"] == sum(sizes)
    assert fs["pages_fetched"] + fs["fetch_hits"] == sum(
        live for _, live in spy.calls)


# --------------------------------------------- mutable tier over streaming
def test_churn_workload_matches_resident_base(dataset, hybrid_artifact):
    """A 95/5-style churn mix (insert batches, base-id tombstones, batched
    reads) over a STREAMED base returns exactly what the same mix over the
    fully resident base returns, at every step."""
    x, q, _ = dataset
    rng = np.random.default_rng(7)
    resident = MutableIndex(PageANNIndex.load(hybrid_artifact))
    streamed = MutableIndex(
        PageANNIndex.load(hybrid_artifact, memory_budget=0.25)
    )
    fresh = rng.standard_normal((40, D)).astype(np.float32)
    for step in range(5):
        rows = np.arange(step * 8, step * 8 + 8)
        ids = N + rows
        resident.insert(fresh[rows], ids=ids)
        streamed.insert(fresh[rows], ids=ids)
        victim = rng.integers(0, N, size=2)
        resident.delete(victim)
        streamed.delete(victim)
        want = resident.search(q, k=10)
        got = streamed.search(q, k=10)
        for field in want._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(want, field)),
                np.asarray(getattr(got, field)),
                err_msg=f"step {step}: {field}",
            )
    assert streamed.fetch_stats()["pages_fetched"] > 0
    assert resident.fetch_stats()["pages_fetched"] == 0


def test_mutable_load_accepts_budget(tmp_path, dataset, hybrid_artifact):
    _, q, _ = dataset
    mut = MutableIndex(PageANNIndex.load(hybrid_artifact))
    mut.insert(np.ones((3, D), np.float32), ids=np.arange(N, N + 3))
    art = str(tmp_path / "mut.delta")
    mut.save(art)
    loaded = MutableIndex.load(art, memory_budget=0.25)
    assert loaded.fetch_stats()["pages_fetched"] == 0     # nothing searched yet
    np.testing.assert_array_equal(
        loaded.search(q, k=10).ids, mut.search(q, k=10).ids
    )
    assert loaded.fetch_stats()["pages_fetched"] > 0


# ------------------------------------------------------- serving surface
def test_engine_metrics_report_fetch_counters(dataset, hybrid_artifact):
    from repro.serve import VectorService

    _, q, _ = dataset
    with VectorService(batch_size=4) as svc:
        svc.attach("res", hybrid_artifact)
        svc.attach("str", hybrid_artifact, memory_budget="0.25")
        want = [r.result.ids for r in svc.search("res", q, k=10)]
        got = [r.result.ids for r in svc.search("str", q, k=10)]
        np.testing.assert_array_equal(np.stack(want), np.stack(got))
        m = svc.metrics()
        assert m.pages_fetched > 0
        assert m.fetch_wall_s >= 0.0
        assert m.pages_fetched + m.fetch_hits > 0


def test_streamed_geometry_never_shares_compiled_key(hybrid_artifact):
    """A streamed index's executable closes over its host fetcher — the
    compile cache must key it apart from the resident geometry (and from
    any other streamed load)."""
    from repro.serve.compile_cache import geometry_of

    full = PageANNIndex.load(hybrid_artifact)
    s1 = PageANNIndex.load(hybrid_artifact, memory_budget=0.25)
    s2 = PageANNIndex.load(hybrid_artifact, memory_budget=0.25)
    assert geometry_of(full) != geometry_of(s1)
    assert geometry_of(s1) != geometry_of(s2)
    assert geometry_of(s1) == geometry_of(s1)


def test_database_load_threads_budget(tmp_path, dataset, hybrid_artifact):
    from repro.serve import VectorService

    _, q, _ = dataset
    db = str(tmp_path / "db")
    with VectorService(batch_size=4) as svc:
        svc.attach("wiki", hybrid_artifact)
        svc.save(db)
    with VectorService.load(db, batch_size=4, memory_budget=0.25) as svc:
        idx = svc.index_of("wiki")
        assert idx.fetcher is not None
        assert idx.stats.resident_pages * 4 <= idx.stats.pages
        rows = svc.search("wiki", q, k=10)
        assert len(rows) == Q
        assert svc.metrics().pages_fetched > 0


def test_baselines_reject_memory_budget(tmp_path, dataset):
    x, _, _ = dataset
    idx = bl.StarlingIndex.build(x, _cfg())
    art = str(tmp_path / "idx.starling")
    idx.save(art)
    with pytest.raises(ValueError, match="memory_budget"):
        load_index(art, memory_budget=0.25)
    # no budget still loads fine
    assert type(load_index(art)) is bl.StarlingIndex


def test_load_index_dispatch_streams_pageann(dataset, hybrid_artifact):
    _, q, _ = dataset
    idx = load_index(hybrid_artifact, memory_budget="0.25")
    assert type(idx) is PageANNIndex and idx.fetcher is not None
    np.testing.assert_array_equal(
        idx.search(q, k=10).ids,
        load_index(hybrid_artifact).search(q, k=10).ids,
    )
