"""End-to-end PageANN search behaviour (Algorithm 2) + memory-mode matrix
+ exact equivalence of the fused/top-k hot path against the frozen seed loop."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import seed_search_ref
from repro.core import (
    MemoryMode,
    PageANNConfig,
    PageANNIndex,
    SearchParams,
    recall_at_k,
)
from repro.core.vamana import brute_force_knn
from repro.data.pipeline import clustered_vectors, query_vectors

N, D, Q = 2500, 32, 25


@pytest.fixture(scope="module")
def dataset():
    x = clustered_vectors(N, D, num_clusters=32, seed=0)
    q = query_vectors(x, Q, seed=1)
    truth = brute_force_knn(x, q, 10)
    return x, q, truth


def _cfg(**kw):
    base = dict(
        dim=D, graph_degree=16, build_beam=32, pq_subspaces=8,
        lsh_sample=512, lsh_entries=8, beam_width=64, max_hops=48,
        memory_mode=MemoryMode.HYBRID,
    )
    base.update(kw)
    return PageANNConfig(**base)


@pytest.fixture(scope="module")
def hybrid_index(dataset):
    x, _, _ = dataset
    return PageANNIndex.build(x, _cfg())


def test_recall_at_10(dataset, hybrid_index):
    x, q, truth = dataset
    res = hybrid_index.search(q, k=10)
    r = recall_at_k(res.ids, truth)
    assert r >= 0.85, r


def test_io_accounting_invariants(dataset, hybrid_index):
    _, q, _ = dataset
    res = hybrid_index.search(q, k=10)
    cfg = hybrid_index.cfg
    assert (res.ios <= res.hops * cfg.io_batch).all()
    assert (res.ios + res.cache_hits >= res.hops).all()   # >=1 fresh page/hop
    assert (res.ios <= hybrid_index.store.num_pages).all()  # visited-set works


@pytest.fixture(scope="module", params=list(MemoryMode), ids=lambda m: m.value)
def mode_index(request, dataset):
    x, _, _ = dataset
    return PageANNIndex.build(x, _cfg(memory_mode=request.param))


def test_memory_modes_all_reach_recall(dataset, mode_index):
    _, q, truth = dataset
    res = mode_index.search(q, k=10)
    r = recall_at_k(res.ids, truth)
    assert r >= 0.8, (mode_index.cfg.memory_mode, r)


def test_optimized_loop_matches_seed_search(dataset, mode_index):
    """The fused page-scan + top-k hot path is a pure speedup: identical
    results, I/O counts, and hop counts to the frozen seed loop (argsort
    merges, serial select, split member/neighbor gathers) on every
    memory-disk coordination mode."""
    _, q, _ = dataset
    qj = jnp.asarray(q, jnp.float32)
    got = mode_index._raw_search(qj, mode_index.resolve_params(10, None))
    want = seed_search_ref.seed_batch_search(qj, mode_index, k=10)
    np.testing.assert_array_equal(np.asarray(got.ios), np.asarray(want.ios))
    np.testing.assert_array_equal(np.asarray(got.hops), np.asarray(want.hops))
    np.testing.assert_array_equal(
        np.asarray(got.cache_hits), np.asarray(want.cache_hits)
    )
    np.testing.assert_allclose(
        np.asarray(got.dists), np.asarray(want.dists), rtol=1e-6, atol=1e-6
    )
    # id sets match row-wise (ordering may differ only across exact ties)
    for i in range(len(q)):
        assert set(np.asarray(got.ids)[i].tolist()) == set(
            np.asarray(want.ids)[i].tolist()
        ), i


@pytest.mark.parametrize("beam", [16, 96, 128])
def test_beam_membership_compare_matches_sorted_probe(dataset, hybrid_index,
                                                      beam):
    """The hop's beam-membership test is a dense compare: it equals
    ``np.isin`` over beams and neighbour lists holding PAD, duplicates and
    absent ids, and ``score_page_batch``'s estimates are bit-identical to
    the sorted ``searchsorted`` probe's masking of the same hop."""
    from repro.core import pq as pq_mod
    from repro.core import search as search_mod

    idx = hybrid_index
    data, cap = idx.data, idx.store.capacity
    nbr_ids = np.asarray(data.nbr_ids)
    num_pages, b = nbr_ids.shape[0], 5
    rng = np.random.default_rng(beam)
    q = jnp.asarray(dataset[1][0], jnp.float32)
    disk_lut = pq_mod.pq_lut(q, data.disk_codebooks)
    mem_lut = pq_mod.pq_lut(q, data.mem_codebooks)
    for _ in range(8):
        batch = rng.choice(num_pages, b, replace=False).astype(np.int32)
        batch[rng.integers(b)] = search_mod.PAD       # an unscheduled lane
        nids = nbr_ids[np.maximum(batch, 0)].reshape(-1)
        # the beam: some of this hop's neighbours (duplicated), ids absent
        # from the hop, and PAD slots
        cand = np.concatenate([
            rng.choice(nids, beam // 2),
            rng.integers(0, num_pages * cap, beam // 4),
            np.full(beam - beam // 2 - beam // 4, search_mod.PAD),
        ]).astype(np.int32)
        rng.shuffle(cand)
        probe = np.append(nids, search_mod.PAD).astype(np.int32)
        got = search_mod.dense_isin(jnp.asarray(probe), jnp.asarray(cand))
        np.testing.assert_array_equal(np.asarray(got), np.isin(probe, cand))

        page_vis = rng.random(num_pages) < 0.1
        # the visited set as the loop keeps it: a trail of the pages
        trail = np.full(idx.default_params.max_hops * b, search_mod.PAD,
                        np.int32)
        trail[:page_vis.sum()] = np.flatnonzero(page_vis)
        state = search_mod.BeamState(
            cand_ids=jnp.asarray(cand),
            cand_d=jnp.zeros((beam,), jnp.float32),
            cand_vis=jnp.zeros((beam,), bool),
            trail=jnp.asarray(trail.reshape(-1, b)),
            res_ids=jnp.full((10,), search_mod.PAD, jnp.int32),
            res_d=jnp.full((10,), jnp.inf, jnp.float32),
            io=jnp.int32(0), cache_hits=jnp.int32(0), hops=jnp.int32(0),
        )

        def est_of(state):
            out = search_mod.score_page_batch(
                q, data, jnp.asarray(batch), state, disk_lut, mem_lut,
                capacity=cap, mode=idx.cfg.memory_mode.value)
            np.testing.assert_array_equal(np.asarray(out[2]), nids)
            return np.asarray(out[3])

        # every mask but the beam's (an all-PAD beam only matches PAD
        # neighbours, which the validity mask drops anyway), then the
        # sorted probe's mask on top
        base = est_of(state._replace(
            cand_ids=jnp.full((beam,), search_mod.PAD, jnp.int32)))
        s = np.sort(cand)
        pos = np.minimum(np.searchsorted(s, nids), beam - 1)
        want = np.where(s[pos] == nids, np.float32(np.inf), base)
        np.testing.assert_array_equal(est_of(state), want)
        assert np.isfinite(want).any() and np.isinf(want).any()


def _bitmap_select(cand, d, vis, visited, cap, b):
    """The seed's b serial argmin picks over a (P,) visited-page bitmap:
    each pick first retires candidates on visited pages, then marks its own
    page visited. Returns (expanded flags, batch, bitmap after)."""
    pad = -1
    vis, visited = vis.copy(), visited.copy()
    pages = np.where(cand >= 0, cand // cap, 0)
    batch = np.full(b, pad, np.int32)
    for j in range(b):
        vis |= (cand != pad) & visited[pages]
        masked = np.where(vis | (cand == pad), np.inf, d)
        s = int(np.argmin(masked))
        vis[s] = True
        if np.isfinite(masked[s]):
            batch[j] = pages[s]
            visited[pages[s]] = True
    return vis, batch, visited


@pytest.mark.parametrize("hops,b,beam", [(64, 5, 96), (64, 5, 256),
                                         (256, 8, 256)],
                         ids=["64x5-beam96", "64x5-beam256", "256x8-beam256"])
def test_trail_membership_matches_a_page_bitmap(dataset, hybrid_index, hops,
                                                b, beam):
    """The loop's visited set is each lane's trail of scheduled pages, and
    its three tests are dense compares against it: ``select_batch``'s stale
    and early tests and ``score_page_batch``'s neighbour test give what a
    (P,) bitmap of the same pages gives, over random trails with PAD slots
    and rows, pages repeated across hops, PAD candidates and neighbours,
    and a lane whose beam is exhausted."""
    from repro.core import pq as pq_mod
    from repro.core import search as search_mod

    pad = search_mod.PAD
    idx = hybrid_index
    cap, mode = idx.store.capacity, idx.cfg.memory_mode.value
    num_pages = idx.store.num_pages
    nbr_ids = np.asarray(idx.data.nbr_ids).copy()
    nbr_ids[::7, -3:] = pad                 # PAD neighbour slots
    data = idx.data._replace(nbr_ids=jnp.asarray(nbr_ids))
    rng = np.random.default_rng(hops * b + beam)
    lanes = 6
    # lane 0 at its first hop, lane 1 at its last, lane 2 exhausted
    at = np.concatenate([[0, hops - 1], rng.integers(1, hops, lanes - 2)])
    trails, cands, dists, viss, visited = [], [], [], [], []
    for lane in range(lanes):
        h = int(at[lane])
        # about half the pages visited, repeats across hops, PAD slots
        p = min(1.0, num_pages / (2 * max(h, 1) * b))
        trail = np.full((hops, b), pad, np.int32)
        for row in range(h):
            n = rng.binomial(b, p)
            trail[row, :n] = rng.choice(num_pages, n, replace=False)
            rng.shuffle(trail[row])
        seen = np.zeros(num_pages, bool)
        seen[trail[trail >= 0]] = True
        # the beam: ids on visited pages, on any page, and PAD slots
        on_seen = np.flatnonzero(np.repeat(seen, cap))
        n_seen = min(beam // 4, len(on_seen))
        ids = np.concatenate([
            rng.choice(on_seen, n_seen, replace=False),
            rng.choice(num_pages * cap, beam // 2, replace=False),
        ])
        ids = np.unique(ids)[:beam - beam // 8]
        cand = np.full(beam, pad, np.int32)
        cand[:len(ids)] = ids
        rng.shuffle(cand)
        d = rng.random(beam).astype(np.float32)
        d[cand == pad] = np.inf
        d[rng.random(beam) < 0.05] = np.inf
        vis = rng.random(beam) < 0.2
        if lane == 2:
            vis[:] = True
        trails.append(trail)
        cands.append(cand)
        dists.append(d)
        viss.append(vis)
        visited.append(seen)

    def stack(xs):
        return jnp.asarray(np.stack(xs))

    state = search_mod.BeamState(
        cand_ids=stack(cands), cand_d=stack(dists), cand_vis=stack(viss),
        trail=stack(trails),
        res_ids=jnp.full((lanes, 10), pad, jnp.int32),
        res_d=jnp.full((lanes, 10), jnp.inf, jnp.float32),
        io=jnp.zeros(lanes, jnp.int32), cache_hits=jnp.zeros(lanes, jnp.int32),
        hops=jnp.asarray(at, jnp.int32))
    new, batch = jax.vmap(functools.partial(
        search_mod.select_batch, capacity=cap, io_batch=b))(state)
    new_vis, batch = np.asarray(new.cand_vis), np.asarray(batch)
    new_trail = np.asarray(new.trail)
    after = []
    for lane in range(lanes):
        vis, want_batch, seen = _bitmap_select(
            cands[lane], dists[lane], viss[lane], visited[lane], cap, b)
        np.testing.assert_array_equal(new_vis[lane], vis)
        np.testing.assert_array_equal(batch[lane], want_batch)
        want_trail = trails[lane].copy()
        want_trail[at[lane]] = want_batch
        np.testing.assert_array_equal(new_trail[lane], want_trail)
        after.append(seen)
    assert (batch[2] == pad).all() and (batch[[0, 1]] >= 0).any()

    q = jnp.asarray(dataset[1][0], jnp.float32)
    disk_lut = pq_mod.pq_lut(q, data.disk_codebooks)
    mem_lut = pq_mod.pq_lut(q, data.mem_codebooks)
    score = jax.vmap(lambda bt, st: search_mod.score_page_batch(
        q, data, bt, st, disk_lut, mem_lut, capacity=cap, mode=mode))
    out = score(jnp.asarray(batch), new)
    nids, got = np.asarray(out[2]), np.asarray(out[3])
    # every mask but the visited test (an all-PAD trail matches no page),
    # then the bitmap's test on top
    base = np.asarray(score(jnp.asarray(batch), new._replace(
        trail=jnp.full_like(new.trail, pad)))[3])
    pages = np.maximum(nids, 0) // cap
    on_seen = np.stack([after[lane][pages[lane]] for lane in range(lanes)])
    np.testing.assert_array_equal(got, np.where(on_seen, np.inf, base))
    assert (nids == pad).any()
    assert (np.isfinite(base) & on_seen).any() and np.isfinite(got).any()


def test_mem_all_packs_more_vectors_per_page(dataset):
    x, _, _ = dataset
    disk = PageANNIndex.build(x, _cfg(memory_mode=MemoryMode.DISK_ONLY))
    mem = PageANNIndex.build(x, _cfg(memory_mode=MemoryMode.MEM_ALL))
    # Sec 4.3(3): freed page bytes -> more vectors per page -> fewer pages
    assert mem.store.capacity > disk.store.capacity
    assert mem.store.num_pages < disk.store.num_pages


def test_page_cache_reduces_counted_ios(dataset):
    x, q, truth = dataset
    idx = PageANNIndex.build(x, _cfg(cache_pages=32))
    before = idx.search(q, k=10)
    idx.warm_cache(q)
    after = idx.search(q, k=10)
    assert after.cache_hits.sum() > 0
    assert after.ios.mean() < before.ios.mean()
    # caching must not change results
    assert recall_at_k(after.ids, truth) >= recall_at_k(before.ids, truth) - 1e-9


def test_results_sorted_and_unique(dataset, hybrid_index):
    _, q, _ = dataset
    res = hybrid_index.search(q, k=10)
    for i in range(len(q)):
        d = res.dists[i]
        assert (np.diff(d[np.isfinite(d)]) >= -1e-6).all()
        ids = res.ids[i][res.ids[i] >= 0]
        assert len(np.unique(ids)) == len(ids)


def test_beam_width_trades_io_for_recall(dataset, hybrid_index):
    """Runtime knobs are per-call SearchParams: the whole beam sweep runs
    over ONE built index, and a point of that sweep is bit-identical to an
    index whose build config froze the same knobs."""
    x, q, truth = dataset
    lo = SearchParams(k=10, beam_width=16, lsh_entries=4, max_hops=48)
    hi = SearchParams(k=10, beam_width=96, lsh_entries=16, max_hops=48)
    res_lo = hybrid_index.search(q, params=lo)
    res_hi = hybrid_index.search(q, params=hi)
    assert recall_at_k(res_hi.ids, truth) >= recall_at_k(res_lo.ids, truth)
    assert res_hi.ios.mean() >= res_lo.ios.mean()

    # the config's knobs are only defaults for the same runtime path:
    # a config-frozen build must reproduce the per-call sweep point exactly
    frozen = PageANNIndex.build(x, _cfg(beam_width=16, lsh_entries=4))
    res_frozen = frozen.search(q, k=10)
    for field in res_lo._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(res_lo, field)),
            np.asarray(getattr(res_frozen, field)),
            err_msg=field,
        )


def test_build_warmup_queries_populate_cache(dataset):
    """Sec 4.3 warm path: build(..., warmup_queries=...) with cache_pages>0
    must leave a populated page cache, and repeat queries must convert
    disk reads into cache hits without changing the read schedule."""
    import dataclasses as dc

    from repro.core import search as search_mod

    x, q, _ = dataset
    idx = PageANNIndex.build(x, _cfg(cache_pages=32), warmup_queries=q)
    cached = np.asarray(idx.tier.cached_pages)
    assert 0 < cached.size <= 32
    assert (np.diff(cached) > 0).all()          # sorted, unique page ids

    warm = idx.search(q, k=10)                  # repeat of the warmup batch
    assert warm.cache_hits.sum() > 0

    # against the same index with the cache emptied: hits come out of ios
    # one for one (the cache reclassifies reads, never reorders them)
    cold_tier = dc.replace(
        idx.tier, cached_pages=jnp.zeros((0,), jnp.int32)
    )
    cold_data = search_mod.make_search_data(idx.store, cold_tier, idx.lsh)
    cold = search_mod.batch_search(
        jnp.asarray(q, jnp.float32),
        cold_data,
        idx.resolve_params(10, None),
        capacity=idx.store.capacity,
        mode=idx.cfg.memory_mode.value,
    )
    assert np.asarray(cold.cache_hits).sum() == 0
    np.testing.assert_array_equal(
        np.asarray(warm.ios) + np.asarray(warm.cache_hits),
        np.asarray(cold.ios),
    )
    assert warm.ios.sum() < np.asarray(cold.ios).sum()


def _recall_reference_loop(found_ids, truth_ids):
    """The pre-vectorization recall_at_k: per-query python set intersection."""
    hits = 0
    q, k = truth_ids.shape
    for i in range(q):
        hits += len(set(found_ids[i].tolist()) & set(truth_ids[i].tolist()))
    return hits / (q * k)


def test_recall_at_k_matches_reference_loop():
    rng = np.random.default_rng(7)
    for _ in range(40):
        qn = int(rng.integers(1, 8))
        kt = int(rng.integers(1, 12))
        kf = int(rng.integers(1, 12))           # found width may differ
        found = rng.integers(-1, 15, (qn, kf))  # duplicates and PAD included
        truth = rng.integers(-1, 15, (qn, kt))
        assert recall_at_k(found, truth) == pytest.approx(
            _recall_reference_loop(found, truth), abs=1e-12
        )


def test_high_dim_vectors_span_multiple_record_rows():
    """dim > 128 packs each member vector over ceil(d/128) record rows —
    the fused hot path must handle standard embedding sizes end to end."""
    d = 160  # rpv = 2, and 160/8 PQ subspaces divides evenly
    x = clustered_vectors(600, d, num_clusters=8, seed=4)
    q = query_vectors(x, 8, seed=5)
    truth = brute_force_knn(x, q, 10)
    idx = PageANNIndex.build(
        x,
        PageANNConfig(
            dim=d, graph_degree=12, build_beam=24, pq_subspaces=8,
            lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
            memory_mode=MemoryMode.HYBRID,
        ),
    )
    res = idx.search(q, k=10)
    assert recall_at_k(res.ids, truth) >= 0.7


def test_layout_equation_capacity():
    cfg = _cfg(page_bytes=4096, pq_subspaces=8, page_degree=48)
    cap = cfg.resolve_capacity()
    # Sec 4.2 equation: (4096 - 8 - 48*4 - 24*8) / (32*4) for HYBRID
    assert cap == (4096 - 8 - 48 * 4 - 24 * 8) // (32 * 4)
