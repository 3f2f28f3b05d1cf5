"""Filtered search with predicates as data: bag-of-tags fields, per-query
planning between the exact posting-list scan and the masked graph, and
dispatch sharing across predicates, each compared with the plain
reference (``filter_mask_np`` plus exact brute force) on seeded data."""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from repro.core import (
    Bag,
    IndexFormatError,
    MemoryMode,
    MetadataSchema,
    PageANNConfig,
    PageANNIndex,
    SearchParams,
    Tag,
    load_index,
    recall_at_k,
)
from repro.core import filter as filter_mod
from repro.core import lsh as lsh_mod
from repro.core import persist
from repro.core.plan import EXACT, GRAPH, Planner, packed_rows
from repro.data.pipeline import clustered_vectors, query_vectors
from repro.serve import BatchingEngine, VectorService

N, D, K, VOCAB, WIDTH = 1000, 32, 10, 300, 8
PAD = -1
# exact path: ids equal the reference's; distances are float32 sums of
# squared differences against the reference's float64, so they agree to
# float32 rounding of values ~1 (random floats make exact ties impossible)
DIST_RTOL = 1e-5
# graph path: approximate search; the floor the repo's filtered parity
# gates hold the graph to
GRAPH_RECALL = 0.9


@pytest.fixture(scope="module")
def data():
    """Clustered vectors with a bag of up to WIDTH Zipf(1)-drawn tags of a
    VOCAB-value vocabulary each, and Q queries with one or two tags of a
    random vector's bag."""
    x = clustered_vectors(N, D, num_clusters=16, seed=0)
    q = query_vectors(x, 64, seed=1)
    rng = np.random.default_rng(3)
    w = 1.0 / np.arange(1, VOCAB + 1)
    bags = [sorted({f"t{c}" for c in rng.choice(VOCAB, rng.integers(1, 9),
                                                p=w / w.sum())})
            for _ in range(N)]
    src = rng.integers(0, N, len(q))
    tags = [list(rng.choice(bags[s], min(1 + i % 2, len(bags[s])),
                            replace=False)) for i, s in enumerate(src)]
    return x, q, bags, tags


def _cfg(**kw):
    base = dict(dim=D, graph_degree=12, build_beam=24, pq_subspaces=8,
                lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
                memory_mode=MemoryMode.HYBRID)
    base.update(kw)
    return PageANNConfig(**base)


SCHEMA = MetadataSchema(tags=("lang",), bags={"labels": WIDTH})


@pytest.fixture(scope="module")
def index(data):
    x, _, bags, _ = data
    lang = np.random.default_rng(5).choice(["en", "de"], N).tolist()
    return PageANNIndex.build(x, _cfg(), schema=SCHEMA,
                              metadata={"labels": bags, "lang": lang})


def _exprs(tags):
    return [Bag("labels").contains_all(*t) for t in tags]


def _reference(idx, x, q, exprs, k=K):
    """filter_mask_np over the host columns, then exact float64 kNN over
    the passing rows: (ids (Q, k), PAD past the matches; dists; masks)."""
    ids = np.full((len(q), k), PAD, np.int64)
    dists = np.full((len(q), k), np.inf)
    masks = []
    for i, e in enumerate(exprs):
        cf = filter_mod.compile_filter(e, idx.schema, idx.vocab)
        mask = filter_mod.filter_mask_np(cf, idx.meta_host.tags,
                                         idx.meta_host.nums)
        rows = np.flatnonzero(mask)
        d = ((x[rows].astype(np.float64) - q[i]) ** 2).sum(-1)
        order = np.argsort(d, kind="stable")[:k]
        ids[i, :len(order)] = rows[order]
        dists[i, :len(order)] = d[order]
        masks.append(mask)
    return ids, dists, masks


def _routed(idx, path):
    """The index with its planner moved so every query of more than a
    few matches takes ``path`` (the derived threshold is the planner's
    to give; here it is set around it)."""
    rows = 10**6 if path == EXACT else 0
    return dataclasses.replace(
        idx, planner=dataclasses.replace(idx.planner, rows_per_page=rows))


@pytest.mark.parametrize("path", [EXACT, GRAPH])
def test_served_answers_match_the_plain_filtered_reference(index, data,
                                                           path):
    x, q, _, tags = data
    idx = _routed(index, path)
    exprs = _exprs(tags)
    plans = [idx.plan(e) for e in exprs]
    on = np.array([p.path == path for p in plans])
    assert on.sum() >= 16, "too few queries on the path to judge it"
    assert {len(t) for t, o in zip(tags, on) if o} == {1, 2}
    with VectorService(batch_size=64) as svc:
        svc.create_collection("c", idx, k=K)
        got = svc.search("c", q, filter=exprs)
    ids = np.stack([np.asarray(r.result.ids) for r in got])
    dists = np.stack([np.asarray(r.result.dists) for r in got])
    want, want_d, masks = _reference(idx, x, q, exprs)
    for i in np.flatnonzero(on):
        served = ids[i][ids[i] != PAD]
        assert masks[i][served].all(), f"query {i}: an id fails its filter"
    if path == EXACT:
        np.testing.assert_array_equal(ids[on], want[on])
        np.testing.assert_allclose(dists[on], want_d[on], rtol=DIST_RTOL)
    else:
        assert recall_at_k(ids[on], want[on]) >= GRAPH_RECALL


def test_the_threshold_edge_routes_each_side(index, data):
    """A query whose match count M equals the derived threshold is scanned
    exactly; one hop less of page budget moves it to the graph."""
    x, q, _, tags = data
    idx = dataclasses.replace(
        index, planner=dataclasses.replace(index.planner, rows_per_page=1))
    # the first query whose predicate has 30..200 matches: enough hops to
    # pass the other two bounds, few enough to search quickly
    i, e, m = next((i, e, idx.plan(e).count)
                   for i, e in enumerate(_exprs(tags))
                   if 30 <= idx.plan(e).count <= 200)
    want, _, (mask,) = _reference(idx, x, q[i:i + 1], [e])
    for hops, path in ((m, EXACT), (m - 1, GRAPH)):
        p = SearchParams(k=K, beam_width=48, io_batch=1, max_hops=hops,
                         lsh_entries=8)
        assert idx.planner.threshold(p, 64) == hops
        assert idx.plan(e, params=p).path == path
        got = np.asarray(idx.search(q[i:i + 1], params=p, filter=e).ids)
        assert mask[got[got != PAD]].all()
        assert recall_at_k(got, want) >= GRAPH_RECALL


def test_planner_threshold_is_the_largest_of_its_three_bounds():
    pl = Planner(live=1000, capacity=28, rows_per_page=10)
    p = SearchParams(k=10, beam_width=16, io_batch=2, max_hops=4,
                     lsh_entries=8)
    # reach 4*2*28 = 224 -> ceil(10*1000/224) = 45; page budget 8 pages of
    # 10 rows = 80; the widest factor 64 fits -> ceil(1000/64) = 16
    assert pl.max_factor(16, 64) == 64
    assert pl.threshold(p, 64) == 80
    cf = filter_mod.compile_filter(Tag("t") == "a",
                                   MetadataSchema(tags=("t",)), {"t": ("a",)})
    ident = np.arange(1000)
    at = pl.plan(cf, np.arange(80), ident, p, 64)
    over = pl.plan(cf, np.arange(81), ident, p, 64)
    assert (at.path, at.bucket) == (EXACT, 3)       # 80 fill 3 rows of 32
    assert (over.path, over.bucket) == (GRAPH, 16)     # 1000 / 81 -> 16
    assert pl.max_factor(16, 4) == 4                   # the cap binds
    small = dataclasses.replace(pl, device_bytes=64 * (16 * 4) ** 2 * 4 * 4)
    assert small.max_factor(16, 64) == 4               # the memory binds


def test_unknown_tag_gives_an_empty_answer(index, data):
    _, q, _, _ = data
    for e in (Bag("labels").contains_all("t0", "no-such-tag"),
              Tag("lang") == "klingon"):
        pl = index.plan(e)
        assert (pl.count, pl.path) == (0, EXACT)
        res = index.search(q[:4], K, filter=e)
        assert (np.asarray(res.ids) == PAD).all()
        assert np.isinf(np.asarray(res.dists)).all()


def test_fewer_matches_than_k_are_all_served_then_pad(index, data):
    x, q, bags, _ = data
    counts = {}
    for b in bags:
        for t in b:
            counts[t] = counts.get(t, 0) + 1
    tag = min(counts, key=lambda t: (counts[t], t))
    e = Bag("labels").contains_all(tag)
    m = counts[tag]
    assert 0 < m < K
    res = index.search(q[:2], K, filter=e)
    want, want_d, _ = _reference(index, x, q[:2], [e, e])
    np.testing.assert_array_equal(np.asarray(res.ids), want)
    assert (np.asarray(res.ids)[:, m:] == PAD).all()
    np.testing.assert_allclose(np.asarray(res.dists)[:, :m], want_d[:, :m],
                               rtol=DIST_RTOL)


class _Compiles:
    def __init__(self):
        self.count = 0

    def __call__(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def test_distinct_predicates_of_one_shape_share_programs_and_dispatches(
        index, data):
    """64 queries with 64 distinct predicates of one shape dispatch once
    per group they touch (the exact path's one, each beam factor of the
    graph's) and compile at most one program each; 64 more distinct
    predicates compile nothing."""
    _, q, bags, _ = data
    rng = np.random.default_rng(11)
    seen: set = set()
    exprs = []
    while len(exprs) < 128:
        b = bags[int(rng.integers(0, N))]
        pick = tuple(sorted(rng.choice(b, min(2, len(b)), replace=False)))
        if pick not in seen:
            seen.add(pick)
            exprs.append(Bag("labels").contains_all(*pick))
    idx = _routed(index, GRAPH)     # both paths: rare tags stay exact
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        with BatchingEngine.from_index(idx, k=K, batch_size=64) as eng:
            groups = {idx.plan(e).group for e in exprs[:64]}
            assert {g[1] for g in groups} == {EXACT, GRAPH}
            futs = [eng.submit(v, filter=e) for v, e in zip(q, exprs[:64])]
            eng.flush()
            [f.result() for f in futs]
            assert eng.metrics().batches == len(groups)
            assert compiles.count <= len(groups)
            later = {idx.plan(e).group for e in exprs[64:]} - groups
            before = compiles.count
            futs = [eng.submit(v, filter=e) for v, e in zip(q, exprs[64:])]
            eng.flush()
            [f.result() for f in futs]
            assert compiles.count - before <= len(later)
            m = eng.metrics()
            assert m.filtered_queries == 128
            assert m.filter_matches == sum(idx.plan(e).count for e in exprs)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def test_a_request_with_a_bad_filter_queues_none_of_its_queries(index,
                                                                data):
    """``submit_many`` plans a block of queries before queuing any: one
    filter the schema does not match refuses the whole block."""
    _, q, _, tags = data
    exprs = _exprs(tags)[:8]
    exprs[5] = Bag("colour").contains_all("red")
    with BatchingEngine.from_index(index, k=K, batch_size=64) as eng:
        with pytest.raises(ValueError, match="unknown bag field"):
            eng.submit_many(q[:8], filters=exprs)
        eng.flush()
        assert eng.metrics().batches == 0


def test_an_exact_plan_wider_than_the_program_is_refused(index, data):
    _, q, _, tags = data
    p = index.resolve_params(K, None)
    slots = index.planner.slots(p, 64)
    pl = index.plan(_exprs(tags)[0])
    wide = pl._replace(path=EXACT, bucket=slots + 1)
    with pytest.raises(ValueError, match="at most"):
        index.search(q[:1], K, filter=[wide])


def test_precompile_covers_every_bucket_a_plan_reaches(index, data):
    _, q, _, tags = data
    p = SearchParams(k=K, beam_width=48, io_batch=5, max_hops=48,
                     lsh_entries=8)
    idx = _routed(index, GRAPH)
    reach = set(idx.planner.buckets(p, 64, 8))
    assert idx.precompile_filtered(_exprs(tags)[0], batch_size=8,
                                   params=p) == len(reach)
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        for e in _exprs(tags)[:16]:
            pl = idx.plan(e, params=p)
            # an exact program's size is its dispatch's rows, packed
            size = (packed_rows(8 * pl.bucket) if pl.path == EXACT
                    else pl.bucket)
            assert (pl.path, size) in reach
            idx.search(q[:8], params=p, filter=[pl] * 8)
        assert compiles.count == 0
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


# ------------------------------------------------------------- filter JSON
def test_parse_filter_reads_each_clause_kind():
    e = filter_mod.parse_filter({"labels": {"all": ["b", "a"]},
                                 "lang": "en", "score": {"ge": 0.5}})
    assert e == (Bag("labels").contains_all("a", "b") & (Tag("lang") == "en")
                 & filter_mod.Num("score").ge(0.5))
    for bad in ({}, {"labels": {"all": "a"}}, {"x": {"near": 1}}, [1]):
        with pytest.raises(ValueError):
            filter_mod.parse_filter(bad)


def test_bag_clause_on_a_tag_field_names_the_kind():
    with pytest.raises(ValueError, match="unknown bag field 'lang' "
                                         r"\(declared tag\)"):
        filter_mod.compile_filter(Bag("lang").contains_all("en"), SCHEMA, {})


def test_schema_rejects_bad_bags():
    with pytest.raises(ValueError) as e:
        MetadataSchema(tags=("a",), bags={"a": 4, "b": 0})
    assert "declared twice" in str(e.value) and "width >= 1" in str(e.value)
    with pytest.raises(ValueError, match="more than its width"):
        filter_mod.normalize_metadata(
            MetadataSchema(bags={"b": 2}), {"b": [["x", "y", "z"]]}, 1)


# ------------------------------------------------------------- persistence
def test_bag_columns_and_postings_round_trip(index, data, tmp_path):
    _, q, _, tags = data
    d = str(tmp_path / "bags.pageann")
    index.save(d)
    loaded = load_index(d)
    assert loaded.schema == SCHEMA and loaded.vocab == index.vocab
    for col in index.postings.offsets:
        np.testing.assert_array_equal(loaded.postings.offsets[col],
                                      index.postings.offsets[col])
        np.testing.assert_array_equal(loaded.postings.ids[col],
                                      index.postings.ids[col])
    exprs = _exprs(tags)
    want = index.search(q, K, filter=exprs)
    got = loaded.search(q, K, filter=exprs)
    for f in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(want, f)),
                                      np.asarray(getattr(got, f)))


def test_bad_bag_sidecars_are_index_format_errors(index, tmp_path):
    d = str(tmp_path / "narrow.pageann")
    index.save(d)
    path = os.path.join(d, persist.META_NPZ)
    with np.load(path) as z:
        tags, nums = z["tags"], z["nums"]
    np.savez(path, tags=tags[:, :-1], nums=nums)       # a bag column short
    with pytest.raises(IndexFormatError, match="shape"):
        load_index(d)
    np.savez(path, tags=np.where(tags >= 0, tags + VOCAB, tags), nums=nums)
    with pytest.raises(IndexFormatError, match="vocabulary"):
        load_index(d)                  # codes no posting list can hold


def test_an_artifact_without_the_new_fields_loads(index, data, tmp_path):
    """The parent's format: no ``bags`` in the schema section, no
    ``lsh_center`` sidecar array (hyperplanes through the origin)."""
    x, q, _, _ = data
    plain = PageANNIndex.build(
        x, _cfg(), schema=MetadataSchema(tags=("lang",)),
        metadata={"lang": ["en"] * N})
    assert plain.lsh.center is None
    d = str(tmp_path / "old.pageann")
    plain.save(d)
    with open(os.path.join(d, persist.MANIFEST)) as f:
        doc = json.load(f)
    assert "bags" not in doc["schema"]
    with np.load(os.path.join(d, persist.ARRAYS_NPZ)) as z:
        assert "lsh_center" not in z.files
    loaded = load_index(d)
    assert loaded.lsh.center is None
    np.testing.assert_array_equal(
        np.asarray(loaded.search(q, K, filter=Tag("lang") == "en").ids),
        np.asarray(plain.search(q, K, filter=Tag("lang") == "en").ids))


# --------------------------------------------------------------------- lsh
def test_lsh_centres_only_where_its_codes_collapse():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2000, 64)).astype(np.float32)
    u8 = np.clip(np.rint(32 * x + 128), 0, 255).astype(np.float32)
    codes = np.zeros((2000, 8), np.uint8)
    centred = lsh_mod.build_lsh(u8, codes, bits=64, sample=1024)
    plain = lsh_mod.build_lsh(x, codes, bits=64, sample=1024)
    assert plain.center is None
    assert centred.center is not None
    planes = np.asarray(centred.planes)
    xs = u8[np.asarray(centred.sample_ids)]
    assert lsh_mod.split_bits(xs, planes) < 32           # collapsed
    assert lsh_mod.split_bits(xs - np.asarray(centred.center), planes) == 64


def test_a_centred_index_round_trips_its_centre(tmp_path):
    x = clustered_vectors(800, D, num_clusters=8, seed=2)
    u8 = np.clip(np.rint(32 * x + 128), 0, 255).astype(np.float32)
    q = np.clip(np.rint(32 * query_vectors(x, 16, seed=3) + 128), 0, 255)
    idx = PageANNIndex.build(u8, _cfg())
    assert idx.lsh.center is not None
    d = str(tmp_path / "u8.pageann")
    idx.save(d)
    loaded = load_index(d)
    np.testing.assert_array_equal(np.asarray(loaded.lsh.center),
                                  np.asarray(idx.lsh.center))
    np.testing.assert_array_equal(np.asarray(loaded.search(q, K).ids),
                                  np.asarray(idx.search(q, K).ids))


def test_the_semantic_cache_keeps_one_predicates_answers_to_itself(
        index, data):
    """Predicates share dispatches, never cached answers: the same
    embedding under another predicate misses the cache."""
    from repro.serve import SemanticCache

    _, q, _, tags = data
    a, b = (Bag("labels").contains_all(*t) for t in tags[:2])
    with VectorService(batch_size=8,
                       semantic_cache=SemanticCache(threshold=0.999)) as svc:
        svc.create_collection("c", index, k=K)
        first = svc.search("c", q[:1], filter=a)[0]
        again = svc.search("c", q[:1], filter=a)[0]
        other = svc.search("c", q[:1], filter=b)[0]
    assert again.cached and not other.cached
    np.testing.assert_array_equal(np.asarray(again.result.ids),
                                  np.asarray(first.result.ids))
    want = index.search(q[:1], K, filter=b)
    np.testing.assert_array_equal(np.asarray(other.result.ids),
                                  np.asarray(want.ids)[0])


def test_a_padding_row_gets_no_answer_and_costs_no_group(index, data):
    """None in the per-query filters marks a batch's padding: PAD ids,
    and the other rows answered as if it were absent."""
    _, q, _, tags = data
    exprs = _exprs(tags[:4])
    got = index.search(q[:6], K, filter=exprs + [None, None])
    want = index.search(q[:4], K, filter=exprs)
    np.testing.assert_array_equal(np.asarray(got.ids)[:4],
                                  np.asarray(want.ids))
    assert (np.asarray(got.ids)[4:] == PAD).all()
