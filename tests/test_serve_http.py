"""HTTP frontend contracts: endpoint surface over a live socket,
admission control (in-flight 503, token-bucket 429, deadline 504),
validation errors, and the shared metrics exposition."""
import gc
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import MemoryMode, PageANNConfig, PageANNIndex
from repro.core.filter import parse_filter
from repro.core.vamana import brute_force_knn
from repro.data.pipeline import clustered_vectors, query_vectors
from repro.obs import parse_prometheus_text, sample_value
from repro.serve import HttpFrontend, TokenBucket, VectorService

N, D, K = 600, 32, 10


@pytest.fixture(scope="module")
def corpus():
    return clustered_vectors(N, D, num_clusters=16, seed=0)


@pytest.fixture(scope="module")
def index(corpus):
    cfg = PageANNConfig(
        dim=D, graph_degree=12, build_beam=24, pq_subspaces=8,
        lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
        memory_mode=MemoryMode.HYBRID,
    )
    return PageANNIndex.build(corpus, cfg)


@pytest.fixture()
def served(index):
    with VectorService(batch_size=16, timeout_ms=5.0) as svc:
        svc.create_collection("wiki", index, k=K)
        with HttpFrontend(svc, port=0, max_inflight=4) as fe:
            yield svc, fe


def _post(url, doc, timeout=60.0):
    req = urllib.request.Request(
        url, json.dumps(doc).encode(), {"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(url, timeout=30.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read()


# -------------------------------------------------------------- endpoints
def test_search_batch_matches_direct(served, corpus):
    svc, fe = served
    q = query_vectors(corpus, 6, seed=3)
    truth = brute_force_knn(corpus, q, K)
    code, doc, _ = _post(fe.url + "/search", {
        "collection": "wiki", "queries": q.tolist(), "k": K,
    })
    assert code == 200 and doc["shed"] == 0
    ids = np.array([r["ids"] for r in doc["results"]])
    assert ids.shape == (6, K)
    hits = sum(
        len(set(map(int, r)) & set(map(int, t)))
        for r, t in zip(ids, truth)
    )
    assert hits / truth.size >= 0.8
    # the HTTP answer is the engine's answer, not an approximation of it
    direct = np.array([
        np.asarray(rr.result.ids).reshape(-1)
        for rr in svc.search("wiki", q, k=K)
    ])
    assert np.array_equal(ids, direct)


def test_single_query_form(served, corpus):
    _, fe = served
    code, doc, _ = _post(fe.url + "/search", {
        "collection": "wiki", "query": corpus[7].tolist(),
    })
    assert code == 200
    assert isinstance(doc["results"], dict)  # unwrapped, not a 1-list
    assert doc["results"]["ids"][0] == 7


def test_collections_healthz_stats(served):
    _, fe = served
    code, body = _get(fe.url + "/collections")
    doc = json.loads(body)
    assert code == 200
    assert {"name": "wiki", "dim": D} in doc["collections"]
    code, body = _get(fe.url + "/healthz")
    assert code == 200 and body == b"ok\n"
    code, body = _get(fe.url + "/stats")
    stats = json.loads(body)
    assert code == 200
    assert "metrics" in stats and "wiki" in stats["collections"]


def test_metrics_exposition_covers_http_and_engine(served, corpus):
    _, fe = served
    _post(fe.url + "/search", {
        "collection": "wiki", "query": corpus[0].tolist(),
    })
    code, body = _get(fe.url + "/metrics")
    assert code == 200
    parsed = parse_prometheus_text(body.decode())
    assert sample_value(
        parsed, "pageann_http_requests_total", route="/search", code="200"
    ) >= 1
    # engine series ride the same registry: one scrape target
    assert sample_value(parsed, "pageann_requests_total") >= 1
    assert sample_value(parsed, "pageann_sheds_total") == 0


# -------------------------------------------------------------- validation
def test_validation_errors(served, corpus):
    _, fe = served
    url = fe.url
    assert _post(url + "/search", {"queries": [[0.0] * D]})[0] == 400
    assert _post(url + "/search", {"collection": "nope",
                                   "queries": [[0.0] * D]})[0] == 404
    assert _post(url + "/search", {"collection": "wiki"})[0] == 400
    assert _post(url + "/search", {"collection": "wiki",
                                   "queries": []})[0] == 400
    assert _post(url + "/search", {"collection": "wiki",
                                   "queries": [[1.0, 2.0]]})[0] == 400
    assert _post(url + "/nope", {})[0] == 404
    # immutable collection: writes are 400, not 500
    assert _post(url + "/insert", {
        "collection": "wiki", "vectors": [corpus[0].tolist()],
    })[0] == 400
    assert _post(url + "/delete", {"collection": "wiki", "ids": [1]})[0] == 400
    req = urllib.request.Request(
        url + "/search", b"{not json", {"Content-Type": "application/json"}
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 400


# -------------------------------------------------------- admission + QoS
def test_rate_limit_429_with_retry_after(index):
    with VectorService(batch_size=16, timeout_ms=5.0) as svc:
        svc.create_collection("wiki", index, k=K)
        with HttpFrontend(
            svc, port=0, rate_limits={"wiki": (0.001, 2.0)}
        ) as fe:
            q = {"collection": "wiki", "query": [0.0] * D}
            codes, headers = [], []
            for _ in range(4):
                c, _, h = _post(fe.url + "/search", q)
                codes.append(c)
                headers.append(h)
            assert codes == [200, 200, 429, 429]
            assert int(headers[2]["Retry-After"]) >= 1
            _, body = _get(fe.url + "/metrics")
            parsed = parse_prometheus_text(body.decode())
            assert sample_value(
                parsed, "pageann_http_rejected_total", reason="ratelimit"
            ) == 2


def test_inflight_cap_503(served, corpus):
    _, fe = served
    # deterministically exhaust the in-flight budget (4), then observe
    # the shed path without relying on races between server threads
    for _ in range(4):
        assert fe._inflight.acquire(blocking=False)
    try:
        code, doc, _ = _post(fe.url + "/search", {
            "collection": "wiki", "query": corpus[0].tolist(),
        })
        assert code == 503 and "overloaded" in doc["error"]
    finally:
        for _ in range(4):
            fe._inflight.release()
    code, _, _ = _post(fe.url + "/search", {
        "collection": "wiki", "query": corpus[0].tolist(),
    })
    assert code == 200  # released capacity admits again
    _, body = _get(fe.url + "/metrics")
    parsed = parse_prometheus_text(body.decode())
    assert sample_value(
        parsed, "pageann_http_rejected_total", reason="inflight"
    ) == 1


def test_deadline_504_counts_engine_sheds(served, corpus):
    _, fe = served
    code, doc, _ = _post(fe.url + "/search", {
        "collection": "wiki", "queries": corpus[:4].tolist(),
        "deadline_ms": 0.001,
    })
    assert code == 504
    _, body = _get(fe.url + "/metrics")
    parsed = parse_prometheus_text(body.decode())
    assert sample_value(parsed, "pageann_sheds_total") == 4
    assert sample_value(
        parsed, "pageann_http_rejected_total", reason="deadline"
    ) == 1


def test_service_healthy_after_sheds(served, corpus):
    _, fe = served
    code, _, _ = _post(fe.url + "/search", {
        "collection": "wiki", "queries": corpus[:2].tolist(),
        "deadline_ms": 0.001,
    })
    assert code == 504
    # a shed batch leaves no poisoned state behind: the very next
    # request on the same group completes normally
    code, doc, _ = _post(fe.url + "/search", {
        "collection": "wiki", "queries": corpus[:2].tolist(),
    })
    assert code == 200 and doc["shed"] == 0
    assert all(r is not None for r in doc["results"])


# ------------------------------------------------------------ token bucket
def test_token_bucket_refill_and_burst():
    t = [0.0]
    b = TokenBucket(rate=2.0, burst=4.0, clock=lambda: t[0])
    assert [b.try_acquire() for _ in range(5)] == [True] * 4 + [False]
    assert b.retry_after_s() == pytest.approx(0.5)
    t[0] += 1.0  # 2 tokens accrue
    assert b.try_acquire() and b.try_acquire() and not b.try_acquire()
    t[0] += 100.0  # refill clamps at burst
    assert [b.try_acquire() for _ in range(5)] == [True] * 4 + [False]


def test_token_bucket_validation():
    with pytest.raises(ValueError):
        TokenBucket(rate=0.0, burst=1.0)
    with pytest.raises(ValueError):
        TokenBucket(rate=1.0, burst=0.0)


# ---------------------------------------------------------- filtered search
@pytest.fixture(scope="module")
def tagged(corpus):
    """The corpus with a bag of tags per vector: vector i holds "a" when
    i % 2 == 0, "b" when i % 3 == 0 and "c" when i % 5 == 0."""
    from repro.core import MetadataSchema

    bags = [[t for t, m in (("a", 2), ("b", 3), ("c", 5)) if i % m == 0]
            for i in range(N)]
    cfg = PageANNConfig(
        dim=D, graph_degree=12, build_beam=24, pq_subspaces=8,
        lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
        memory_mode=MemoryMode.HYBRID,
    )
    return PageANNIndex.build(corpus, cfg,
                              schema=MetadataSchema(bags={"labels": 3}),
                              metadata={"labels": bags})


def test_search_with_per_query_filters_serves_passing_ids(tagged, corpus):
    q = query_vectors(corpus, 4, seed=5)
    filters = [{"labels": {"all": t}} for t in
               (["a"], ["a", "b"], ["b", "c"], ["c"])]
    with VectorService(batch_size=16) as svc:
        svc.create_collection("tagged", tagged, k=K)
        with HttpFrontend(svc, port=0) as fe:
            code, doc, _ = _post(fe.url + "/search", {
                "collection": "tagged", "queries": q.tolist(), "k": K,
                "filters": filters,
            })
            assert code == 200, doc
            one, single, _ = _post(fe.url + "/search", {
                "collection": "tagged", "query": q[1].tolist(), "k": K,
                "filter": filters[1],
            })
            # the batch dispatches once per plan group its predicates
            # fall in, the single query once more
            groups = {tagged.plan(parse_filter(f), k=K).group
                      for f in filters}
            assert svc.metrics().filtered_dispatches == len(groups) + 1
    assert one == 200
    assert single["results"]["ids"] == doc["results"][1]["ids"]
    for i, (r, m) in enumerate(zip(doc["results"], (2, 6, 15, 5))):
        ids = np.array(r["ids"])
        assert (ids >= 0).all() and (ids % m == 0).all()
        # the exact K nearest of the passing rows, nearest first
        rows = np.arange(0, N, m)
        d = ((corpus[rows].astype(np.float64) - q[i]) ** 2).sum(-1)
        np.testing.assert_array_equal(ids, rows[np.argsort(d)[:K]])


def test_search_with_an_unknown_filter_field_is_400(tagged, corpus):
    with VectorService(batch_size=16) as svc:
        svc.create_collection("tagged", tagged, k=K)
        with HttpFrontend(svc, port=0) as fe:
            for doc in (
                {"query": corpus[0].tolist(),
                 "filter": {"colour": {"all": ["red"]}}},
                {"queries": corpus[:2].tolist(),
                 "filters": [{"labels": {"all": ["a"]}}]},   # one for two
                {"query": corpus[0].tolist(),
                 "filter": {"labels": {"near": ["a"]}}},
            ):
                code, body, _ = _post(fe.url + "/search",
                                      dict(doc, collection="tagged", k=K))
                assert code == 400, body
            code, body, _ = _post(fe.url + "/search", {
                "collection": "tagged", "k": K, "query": corpus[0].tolist(),
                "filter": {"colour": {"all": ["red"]}}})
            assert "unknown bag field 'colour'" in body["error"]
            assert svc.metrics().requests == 0


def test_the_frontend_freezes_what_was_loaded_while_it_serves(index):
    """What is alive when a frontend starts (the attached index among it)
    is out of the collector's generations while it serves, so a full
    collection does not rescan it, and back in them once it closes."""
    gc.unfreeze()
    with VectorService(batch_size=16) as svc:
        svc.create_collection("wiki", index, k=K)
        with HttpFrontend(svc, port=0):
            assert gc.get_freeze_count() > 0
        assert gc.get_freeze_count() == 0
