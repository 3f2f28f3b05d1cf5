"""Filtered search: schema/expression validation, in-scan masking recall
parity vs a post-filter brute force across MemoryModes + the streamed
tier, persistence round-trips, mutable-tier filtering, and engine
grouping of predicates that differ only in value."""
import json
import math
import os

import numpy as np
import pytest

from repro.core import (
    IndexFormatError,
    MemoryBudget,
    MemoryMode,
    MetadataSchema,
    MutableIndex,
    Num,
    PageANNConfig,
    PageANNIndex,
    Tag,
    load_index,
    recall_at_k,
)
from repro.core import filter as filter_mod
from repro.core import persist
from repro.core.filter import FilterExpr, compile_filter, filter_mask_np
from repro.data.pipeline import clustered_vectors, query_vectors
from repro.serve import BatchingEngine

N, D, Q, K = 1200, 32, 8, 10
PAD = -1
MODES = (MemoryMode.DISK_ONLY, MemoryMode.HYBRID, MemoryMode.MEM_ALL)
SELECTIVITIES = (0.5, 0.1, 0.01)


def _cfg(**kw):
    base = dict(
        dim=D, graph_degree=12, build_beam=24, pq_subspaces=8,
        lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
        memory_mode=MemoryMode.HYBRID,
    )
    base.update(kw)
    return PageANNConfig(**base)


@pytest.fixture(scope="module")
def dataset():
    x = clustered_vectors(N, D, num_clusters=16, seed=0)
    q = query_vectors(x, Q, seed=1)
    rng = np.random.default_rng(7)
    meta = {
        "lang": rng.choice(["en", "de", "fr"], N).tolist(),
        "score": rng.uniform(0.0, 1.0, N).tolist(),
    }
    return x, q, meta


SCHEMA = MetadataSchema(tags=("lang",), numerics=("score",))


@pytest.fixture(scope="module")
def indexes(dataset):
    """One with-metadata build per MemoryMode (the expensive part,
    shared by every parity case)."""
    x, _, meta = dataset
    return {
        mode: PageANNIndex.build(
            x, _cfg(memory_mode=mode), schema=SCHEMA, metadata=meta
        )
        for mode in MODES
    }


def _oracle(x, q, mask, k):
    """Post-filter brute force: exact top-k over passing rows only."""
    idx = np.flatnonzero(mask)
    take = min(k, len(idx))
    d = ((q[:, None, :] - x[idx][None]) ** 2).sum(-1)
    out = np.full((len(q), k), PAD, np.int64)
    out[:, :take] = idx[np.argsort(d, axis=1)[:, :take]]
    return out


def _host_mask(idx, expr):
    cf, _ = idx.compiled_filter(expr)
    return filter_mask_np(cf, idx.meta_host.tags, idx.meta_host.nums)


# ------------------------------------------------------------- validation
def test_schema_reports_every_violation_in_one_error():
    with pytest.raises(ValueError) as e:
        MetadataSchema(tags=("ok", "ok", "not an id"),
                       numerics=("ok", "x", "x"))
    msg = str(e.value)
    assert "duplicate tags" in msg
    assert "duplicate numerics" in msg
    assert "identifiers" in msg
    assert "both tag and numeric" in msg
    with pytest.raises(ValueError, match="at least one field"):
        MetadataSchema()


def test_expr_validation_and_canonical_hashing():
    with pytest.raises(ValueError) as e:
        FilterExpr(tag_clauses=(("f", ()),),
                   num_clauses=(("g", 2.0, 1.0), ("h", math.nan, 0.0)))
    msg = str(e.value)
    assert "empty value set" in msg and "lo > hi" in msg and "NaN" in msg
    with pytest.raises(ValueError, match="at least one clause"):
        FilterExpr()
    # clause order must not matter: engine group keys and the compile
    # cache both hash the expression
    a = Tag("lang").isin("en", "de") & Num("score").le(0.5)
    b = Num("score").le(0.5) & Tag("lang").isin("de", "en")
    assert a == b and hash(a) == hash(b)


def test_compile_reports_unknown_fields_with_kind_hints():
    expr = (Tag("nope").isin("x") & Tag("score").isin("x")
            & Num("lang").ge(0))
    with pytest.raises(ValueError) as e:
        compile_filter(expr, SCHEMA, {})
    msg = str(e.value)
    assert "unknown tag field 'nope'" in msg
    assert "unknown tag field 'score' (declared numeric)" in msg
    assert "unknown numeric field 'lang' (declared tag)" in msg


def test_filter_on_schemaless_index_is_an_error(dataset):
    x, q, _ = dataset
    idx = PageANNIndex.build(x[:300], _cfg())
    with pytest.raises(ValueError, match="no MetadataSchema"):
        idx.search(q, K, filter=Tag("lang") == "en")


def test_unknown_tag_value_matches_nothing(indexes, dataset):
    _, q, _ = dataset
    idx = indexes[MemoryMode.HYBRID]
    res = idx.search(q, K, filter=Tag("lang") == "klingon")
    assert np.all(np.asarray(res.ids) == PAD)


def test_metadata_normalization_reports_all_problems(dataset):
    x, _, _ = dataset
    with pytest.raises(ValueError) as e:
        filter_mod.normalize_metadata(
            SCHEMA, {"bogus": [1] * 5, "score": [1.0] * 3}, 5
        )
    msg = str(e.value)
    assert "unknown metadata field 'bogus'" in msg
    assert "3 entries for 5 vectors" in msg


# ----------------------------------------------------- recall parity gates
@pytest.mark.parametrize("mode", MODES, ids=lambda m: m.value)
def test_filtered_recall_matches_postfilter_oracle(indexes, dataset, mode):
    x, q, meta = dataset
    idx = indexes[mode]
    scores = np.asarray(meta["score"])
    for sel in SELECTIVITIES:
        expr = Num("score").le(float(np.quantile(scores, sel)))
        truth = _oracle(x, q, _host_mask(idx, expr), K)
        res = idx.search(q, K, filter=expr)
        rec = recall_at_k(res.ids, truth)
        assert rec >= 0.9, f"{mode.value} sel={sel}: recall {rec}"
        # every returned id actually passes the predicate
        passing = set(np.flatnonzero(_host_mask(idx, expr)).tolist())
        got = np.asarray(res.ids)
        assert set(got[got != PAD].tolist()) <= passing


def test_conjunction_tag_and_numeric(indexes, dataset):
    x, q, meta = dataset
    idx = indexes[MemoryMode.HYBRID]
    expr = Tag("lang").isin("en", "de") & Num("score").between(0.2, 0.8)
    truth = _oracle(x, q, _host_mask(idx, expr), K)
    res = idx.search(q, K, filter=expr)
    assert recall_at_k(res.ids, truth) >= 0.9


@pytest.mark.parametrize("mode", MODES, ids=lambda m: m.value)
def test_no_filter_is_bit_identical_to_metadata_free_build(
    indexes, dataset, mode
):
    x, q, _ = dataset
    plain = PageANNIndex.build(x, _cfg(memory_mode=mode))
    want, got = plain.search(q, K), indexes[mode].search(q, K)
    for f in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, f)), np.asarray(getattr(got, f)),
            err_msg=f"{mode.value}: {f}",
        )


def test_streamed_filtered_search_is_bit_identical(indexes, dataset,
                                                   tmp_path):
    x, q, meta = dataset
    idx = indexes[MemoryMode.HYBRID]
    d = str(tmp_path / "streamed.pageann")
    idx.save(d)
    streamed = load_index(d, memory_budget=MemoryBudget(fraction=0.25))
    scores = np.asarray(meta["score"])
    for sel in SELECTIVITIES:
        expr = Num("score").le(float(np.quantile(scores, sel)))
        want = idx.search(q, K, filter=expr)
        got = streamed.search(q, K, filter=expr)
        for f in want._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(want, f)), np.asarray(getattr(got, f)),
                err_msg=f"sel={sel}: {f}",
            )


# ------------------------------------------------------------- persistence
def test_persist_round_trip_keeps_filtering(indexes, dataset, tmp_path):
    _, q, _ = dataset
    idx = indexes[MemoryMode.HYBRID]
    d = str(tmp_path / "rt.pageann")
    idx.save(d)
    assert os.path.isfile(os.path.join(d, persist.META_NPZ))
    loaded = load_index(d)
    assert loaded.schema == SCHEMA and loaded.vocab == idx.vocab
    expr = Tag("lang") == "en"
    want, got = idx.search(q, K, filter=expr), loaded.search(q, K, filter=expr)
    np.testing.assert_array_equal(want.ids, got.ids)
    np.testing.assert_array_equal(want.dists, got.dists)


def _manifest(d):
    with open(os.path.join(d, persist.MANIFEST)) as f:
        return json.load(f)


def _write_manifest(d, doc):
    with open(os.path.join(d, persist.MANIFEST), "w") as f:
        json.dump(doc, f)


def test_load_errors_are_index_format_errors(indexes, tmp_path):
    idx = indexes[MemoryMode.HYBRID]

    # sidecar deleted but manifest still declares a schema
    d1 = str(tmp_path / "no_sidecar.pageann")
    idx.save(d1)
    os.remove(os.path.join(d1, persist.META_NPZ))
    with pytest.raises(IndexFormatError, match="meta.npz"):
        load_index(d1)

    # manifest schema section dropped but the sidecar is present
    d2 = str(tmp_path / "no_schema.pageann")
    idx.save(d2)
    doc = _manifest(d2)
    del doc["schema"]
    _write_manifest(d2, doc)
    with pytest.raises(IndexFormatError, match="schema"):
        load_index(d2)

    # sidecar shape disagrees with the manifest schema
    d3 = str(tmp_path / "bad_shape.pageann")
    idx.save(d3)
    with np.load(os.path.join(d3, persist.META_NPZ)) as z:
        tags, nums = z["tags"], z["nums"]
    np.savez(os.path.join(d3, persist.META_NPZ),
             tags=tags[:, :0], nums=nums)
    with pytest.raises(IndexFormatError, match="shape"):
        load_index(d3)

    # garbled schema section is a format error, not a KeyError
    d4 = str(tmp_path / "garbled.pageann")
    idx.save(d4)
    doc = _manifest(d4)
    doc["schema"] = {"tags": 13}
    _write_manifest(d4, doc)
    with pytest.raises(IndexFormatError):
        load_index(d4)


# ------------------------------------------------------------ mutable tier
def test_mutable_insert_metadata_filterable_immediately(dataset):
    x, q, meta = dataset
    base = PageANNIndex.build(
        x[:800], _cfg(),
        schema=SCHEMA,
        metadata={k: v[:800] for k, v in meta.items()},
    )
    mut = MutableIndex(base, auto_compact=False)
    fresh = x[800:810]
    new_ids = mut.insert(
        fresh,
        metadata={"lang": ["xx"] * 10, "score": [0.5] * 10},
    )
    # "xx" is a NEW tag value: the unified vocab grows append-only, base
    # codes stay stable, and the fresh rows are filterable with no rebuild
    assert "xx" in mut.vocab["lang"]
    res = mut.search(fresh, k=1, filter=Tag("lang") == "xx")
    assert set(np.asarray(res.ids)[:, 0].tolist()) == set(new_ids.tolist())
    # base-tier rows still match their original tags through the delta path
    res_en = mut.search(q, K, filter=Tag("lang") == "en")
    assert np.all(np.asarray(res_en.ids) < 800)

    # compaction re-encodes both tiers under a fresh vocab; the filtered
    # answer set is unchanged
    before = mut.search(fresh, k=1, filter=Tag("lang") == "xx")
    assert mut.compact()
    after = mut.search(fresh, k=1, filter=Tag("lang") == "xx")
    np.testing.assert_array_equal(
        np.asarray(before.ids), np.asarray(after.ids)
    )


def test_mutable_save_load_round_trips_metadata(dataset, tmp_path):
    x, q, meta = dataset
    base = PageANNIndex.build(
        x[:600], _cfg(),
        schema=SCHEMA,
        metadata={k: v[:600] for k, v in meta.items()},
    )
    mut = MutableIndex(base, auto_compact=False)
    mut.insert(x[600:605],
               metadata={"lang": ["zz"] * 5, "score": [0.9] * 5})
    d = str(tmp_path / "mut.pageann")
    mut.save(d)
    loaded = load_index(d)
    assert isinstance(loaded, MutableIndex)
    assert loaded.vocab == mut.vocab
    expr = Tag("lang") == "zz"
    want = mut.search(q, K, filter=expr)
    got = loaded.search(q, K, filter=expr)
    np.testing.assert_array_equal(
        np.asarray(want.ids), np.asarray(got.ids)
    )


# ------------------------------------------------------- engine (satellite)
def test_engine_groups_by_filter_and_matches_direct_search(indexes, dataset):
    """Predicates of one shape planned to one path and bucket carry their
    values as data: three distinct predicates share ONE dispatch, and each
    request still gets its own predicate's answer."""
    _, q, _ = dataset
    idx = indexes[MemoryMode.HYBRID]
    preds = [Tag("lang") == v for v in ("en", "de", "fr")]
    assert len({idx.plan(f).group for f in preds}) == 1
    with BatchingEngine.from_index(idx, k=K, batch_size=64) as eng:
        futs = [eng.submit(v, filter=f) for f in preds for v in q]
        eng.flush()
        rows = [f.result() for f in futs]
        # 3 predicates of one shape -> 1 dispatch of 24 requests
        m = eng.metrics()
        assert m.batches == 1
        assert (m.filtered_queries, m.filtered_dispatches) == (3 * Q, 1)
    for chunk, flt in enumerate(preds):
        got = np.stack(
            [r.result.ids for r in rows[chunk * Q:(chunk + 1) * Q]]
        )
        want = idx.search(q, K, filter=flt)
        np.testing.assert_array_equal(got, np.asarray(want.ids))


def test_raw_search_fn_backend_rejects_filter():
    from repro.core.search import SearchResult

    def toy(q, k, params):
        b = len(q)
        z = np.zeros((b,), np.int32)
        return SearchResult(
            ids=np.zeros((b, k), np.int64),
            dists=np.zeros((b, k), np.float32),
            ios=z, hops=z, cache_hits=z,
        )

    with BatchingEngine(toy, dim=4, batch_size=2, default_k=3) as eng:
        with pytest.raises(ValueError, match="does not support filtered"):
            eng.submit(np.zeros(4, np.float32), filter=Tag("x") == "y")


# ----------------------------------------------------- property (hypothesis)
def test_random_predicates_match_oracle_property(indexes, dataset):
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    x, q, meta = dataset
    scores = np.asarray(meta["score"])

    @hyp.settings(max_examples=15, deadline=None)
    @hyp.given(
        langs=st.sets(st.sampled_from(["en", "de", "fr"]), min_size=1),
        lo=st.floats(0.0, 1.0),
        width=st.floats(0.05, 1.0),
        mode=st.sampled_from(MODES),
    )
    def check(langs, lo, width, mode):
        idx = indexes[mode]
        expr = (Tag("lang").isin(*sorted(langs))
                & Num("score").between(lo, lo + width))
        mask = _host_mask(idx, expr)
        res = idx.search(q, K, filter=expr)
        got = np.asarray(res.ids)
        passing = set(np.flatnonzero(mask).tolist())
        assert set(got[got != PAD].tolist()) <= passing
        if mask.sum() >= K:
            truth = _oracle(x, q, mask, K)
            assert recall_at_k(got, truth) >= 0.9

    check()
