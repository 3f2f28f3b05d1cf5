"""Kind ``tagged_l2``: uint8-valued vectors with a bag of tags each,
queried by a vector and one or two tags ANDed, judged under squared L2.

The big-ann-benchmarks NeurIPS'23 filter track's shape (YFCC-10M): each
vector carries a bag of tags from a large vocabulary, each query is one
vector plus one or two tags, and a vector qualifies only if it carries
every tag of the query. The truth is the exact k nearest among the
vectors that qualify. bench/spec.py sets out what each function of a
kind gives.

The data (the configuration's ``assumed`` section says why each choice):

  vectors  ``clusters`` Gaussian centres (unit normal), each vector one
           centre plus ``cluster_scale`` times unit normal noise, mapped
           affinely by ``u8_scale`` and ``u8_offset``, rounded and
           clipped to 0..255, and stored as float32: every squared
           distance is an integer below 2**24, exact in float32.
  tags     per vector a geometric count of mean ``tags_mean``, capped at
           ``tags_max``; each tag is drawn with probability
           ``own_tag_share`` from the vector's cluster's own Zipf(1) over
           a permutation of the vocabulary of that cluster, otherwise
           from a Zipf(1) over the whole vocabulary; repeats collapse, so
           a bag is a set of tag codes. Tag ``c`` is named ``t<c>``.
  queries  a fresh draw of the same model (a held-out image) from the
           cluster of a random corpus vector, with one tag (even pool
           rows) or two (odd rows) of that vector's bag; a draw is kept
           only where at least ``k`` vectors carry every tag of it.

The module imports numpy only at its top: the load generator, which
never imports JAX, loads it for ``encoded`` and ``body``.
"""
from __future__ import annotations

import json
import math

import numpy as np

from bench import reference
from bench.corpus import BASE_SEED, CORPUS, POOL, Data, rng

FIELD = "labels"          # the bag field's name in the index's schema
# sub-streams of the corpus's and the pool's seeds
_VECTORS, _TAGS, _PERMS = 0, 1, 2
# a (row, tag) pair is the key row << _KEY | code: sorted keys make a
# corpus-wide membership test one binary search
_KEY = 32


def _quantize(v: np.ndarray, cfg: dict) -> np.ndarray:
    u8 = np.rint(np.float32(cfg["u8_scale"]) * v + np.float32(cfg["u8_offset"]))
    return np.clip(u8, 0, 255).astype(np.float32)


def _centres(cfg: dict) -> tuple[np.ndarray, np.random.Generator]:
    r = rng(BASE_SEED, CORPUS, _VECTORS)
    return r.standard_normal((cfg["clusters"], cfg["dim"]),
                             dtype=np.float32), r


def _zipf_cdf(v: int) -> np.ndarray:
    w = 1.0 / np.arange(1, v + 1, dtype=np.float64)
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def _perms(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """Per cluster an affine permutation r -> (a r + b) mod V of the
    vocabulary (a coprime to V): the order of that cluster's own tags."""
    v = cfg["vocabulary"]
    r = rng(BASE_SEED, CORPUS, _PERMS)
    a = np.empty(cfg["clusters"], np.int64)
    for c in range(len(a)):
        while True:
            x = int(r.integers(1, v))
            if math.gcd(x, v) == 1:
                a[c] = x
                break
    return a, r.integers(0, v, cfg["clusters"])


def make_corpus(cfg: dict) -> tuple[np.ndarray, np.ndarray, list]:
    """((N, dim) float32 vectors, (N,) cluster of each, per-row tag code
    arrays (int64, ascending, distinct))."""
    centres, r = _centres(cfg)
    n = cfg["num_vectors"]
    assign = r.integers(0, cfg["clusters"], n)
    noise = r.standard_normal((n, cfg["dim"]), dtype=np.float32)
    x = _quantize(centres[assign]
                  + np.float32(cfg["cluster_scale"]) * noise, cfg)

    t = rng(BASE_SEED, CORPUS, _TAGS)
    v = cfg["vocabulary"]
    counts = np.minimum(t.geometric(1.0 / cfg["tags_mean"], n),
                        cfg["tags_max"])
    row = np.repeat(np.arange(n), counts)
    ranks = np.searchsorted(_zipf_cdf(v), t.random(len(row)), side="right")
    ranks = np.minimum(ranks, v - 1)
    own = t.random(len(row)) < cfg["own_tag_share"]
    a, b = _perms(cfg)
    c = assign[row]
    codes = np.where(own, (a[c] * ranks + b[c]) % v, ranks)
    # one bag per row: distinct codes, ascending
    key = np.unique(row.astype(np.int64) * v + codes)
    rows, codes = key // v, key % v
    cuts = np.searchsorted(rows, np.arange(1, n))
    return np.ascontiguousarray(x), assign, np.split(codes, cuts)


def postings(bags: list, vocabulary: int) -> tuple[np.ndarray, np.ndarray]:
    """CSR over tag codes of the corpus rows carrying each: (offsets
    (V+1,), rows ascending within each code)."""
    lens = np.fromiter((len(b) for b in bags), np.int64, len(bags))
    row = np.repeat(np.arange(len(bags)), lens)
    code = np.concatenate(bags) if len(bags) else np.zeros(0, np.int64)
    order = np.argsort(code, kind="stable")
    offsets = np.concatenate(
        [[0], np.cumsum(np.bincount(code, minlength=vocabulary))])
    return offsets, row[order]


def _carriers(post, tags) -> np.ndarray:
    """The rows (ascending) whose bag holds every code of ``tags``."""
    offsets, rows = post
    lists = sorted((rows[offsets[c]:offsets[c + 1]] for c in tags), key=len)
    out = lists[0]
    for other in lists[1:]:
        out = out[np.isin(out, other, assume_unique=True)]
    return out


def make_pool(cfg: dict, mix: dict, assign, bags, post) -> dict:
    """The query pool: (pool, dim) float32 vectors and (pool, 2) int64
    tag codes, -1 where a query has one tag."""
    centres, _ = _centres(cfg)
    r = rng(BASE_SEED, POOL)
    size, k = mix["pool"], mix["k"]
    queries = np.empty((size, cfg["dim"]), np.float32)
    tags = np.full((size, 2), -1, np.int64)
    i = 0
    while i < size:
        src = int(r.integers(0, len(bags)))
        want = 1 + i % 2
        bag = bags[src]
        if len(bag) < want:
            continue
        pick = np.sort(r.choice(bag, want, replace=False))
        noise = r.standard_normal(cfg["dim"], dtype=np.float32)
        if len(_carriers(post, pick)) < k:
            continue
        queries[i] = _quantize(
            centres[assign[src]] + np.float32(cfg["cluster_scale"]) * noise,
            cfg)
        tags[i, :want] = pick
        i += 1
    return {"queries": queries, "tags": tags}


def data(cfg: dict, mix: dict) -> Data:
    x, assign, bags = make_corpus(cfg)
    post = postings(bags, cfg["vocabulary"])
    lens = np.fromiter((len(b) for b in bags), np.int64, len(bags))
    keys = (np.repeat(np.arange(len(bags), dtype=np.int64), lens) << _KEY
            | np.concatenate(bags))
    return Data({"vectors": x, "tag_keys": keys, "post_offsets": post[0],
                 "post_rows": post[1]},
                make_pool(cfg, mix, assign, bags, post))


def bags_of(data: Data) -> list:
    """Per corpus row its tag codes, ascending."""
    keys = data.corpus["tag_keys"]
    rows, codes = keys >> _KEY, keys & ((1 << _KEY) - 1)
    n = len(data.corpus["vectors"])
    return np.split(codes, np.searchsorted(rows, np.arange(1, n)))


def digest_inputs(cfg: dict) -> dict:
    return {"corpus": {k: cfg[k] for k in (
        "num_vectors", "dim", "clusters", "cluster_scale", "u8_scale",
        "u8_offset", "vocabulary", "tags_mean", "tags_max",
        "own_tag_share")}, "index": cfg["index"]}


def _name(code) -> str:
    return f"t{int(code)}"


def build(cfg: dict, data: Data, path: str) -> None:
    from repro.core import MemoryMode, MetadataSchema, PageANNConfig, PageANNIndex

    ix = cfg["index"]
    labels = [[_name(c) for c in bag] for bag in bags_of(data)]
    PageANNIndex.build(
        data.corpus["vectors"], PageANNConfig(
            dim=cfg["dim"], graph_degree=ix["graph_degree"],
            build_beam=ix["build_beam"], pq_subspaces=ix["pq_subspaces"],
            page_bytes=ix["page_bytes"], lsh_sample=ix["lsh_sample"],
            seed=ix["seed"], memory_mode=MemoryMode(ix["memory_mode"]),
        ),
        schema=MetadataSchema(bags={FIELD: cfg["tags_max"]}),
        metadata={FIELD: labels},
    ).save(path)


def attach(svc, collection: str, path: str, cfg: dict, k: int,
           memory_budget) -> None:
    """Attach the saved index, then compile every (path, bucket) program
    a query of one or two tags can be planned to at the serving batch, so
    the run's warm-up requests compile nothing."""
    from repro.core import Bag, SearchParams

    params = SearchParams(k=k, **cfg["search"])
    svc.attach(collection, path, params=params, memory_budget=memory_budget)
    svc.index_of(collection).precompile_filtered(
        Bag(FIELD).contains_all(_name(0), _name(1)),
        batch_size=cfg["serving"]["batch_size"], params=params)


def record_bytes(index) -> int:
    recs = index.data.page_recs
    return int(np.prod(recs.shape[1:])) * recs.dtype.itemsize


def encoded(pool: dict) -> list[str]:
    """Per pool query the JSON of its vector (integers, as the values
    are) and of its filter, joined by a tab (neither holds one)."""
    out = []
    vectors = pool["queries"].astype(np.int64).tolist()
    for q, t in zip(vectors, pool["tags"].tolist()):
        tags = [_name(c) for c in t if c >= 0]
        out.append(json.dumps(q) + "\t"
                   + json.dumps({FIELD: {"all": tags}}))
    return out


def body(s: dict, frags: list[str], qidx: np.ndarray) -> bytes:
    head = f'{{"collection": {json.dumps(s["collection"])}, "k": {s["k"]}, '
    parts = [frags[i].split("\t") for i in qidx]
    if len(qidx) == 1:
        return (head + f'"query": {parts[0][0]}, '
                f'"filter": {parts[0][1]}}}').encode()
    return (head + '"queries": [' + ", ".join(p[0] for p in parts)
            + '], "filters": [' + ", ".join(p[1] for p in parts)
            + "]}").encode()


def _post(data: Data):
    return data.corpus["post_offsets"], data.corpus["post_rows"]


def _qualifying(data: Data, i: int) -> np.ndarray:
    tags = data.pool["tags"][i]
    return _carriers(_post(data), tags[tags >= 0])


def _knn(x: np.ndarray, q: np.ndarray, rows: np.ndarray, k: int,
         dist) -> tuple[np.ndarray, np.ndarray]:
    """The k nearest of ``rows`` (ascending ids) by ``dist``, ties broken
    by id; -1 / inf past the rows there are."""
    d = dist(x[rows], q)
    order = np.argsort(d, kind="stable")[:k]
    ids = np.full(k, -1, np.int64)
    out = np.full(k, np.inf, np.float32)
    ids[:len(order)] = rows[order]
    out[:len(order)] = d[order]
    return ids, out


def _exact(xs: np.ndarray, q: np.ndarray) -> np.ndarray:
    diff = xs.astype(np.float64) - q.astype(np.float64)[None, :]
    return np.einsum("nd,nd->n", diff, diff)


def truth(data: Data, asked: np.ndarray,
          k: int) -> tuple[np.ndarray, np.ndarray]:
    """Exact float64 squared L2 over the vectors that carry every tag of
    the query, ties broken by id; the distances are the float32 sums of
    squared differences of the ids chosen (exact integers here)."""
    x, pool = data.corpus["vectors"], data.pool["queries"]
    ids = np.empty((len(asked), k), np.int64)
    for j, i in enumerate(asked):
        ids[j] = _knn(x, pool[i], _qualifying(data, i), k, _exact)[0]
    return ids, distances(data, asked, ids)


def distances(data: Data, qidx: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """The exact float32 squared L2 distance of each served id, or +inf
    where the id lacks a tag of its query (so ``dist_gap`` fails any
    answer that breaks the predicate)."""
    x, pool = data.corpus["vectors"], data.pool["queries"]
    safe = np.clip(ids, 0, len(x) - 1)
    d = reference.sq_dists(x, pool[qidx], safe)
    keys = data.corpus["tag_keys"]
    ok = np.ones(ids.shape, bool)
    for t in data.pool["tags"][qidx].T:          # the first, then second tag
        want = safe.astype(np.int64) << _KEY | np.maximum(t, 0)[:, None]
        at = np.minimum(np.searchsorted(keys, want), len(keys) - 1)
        ok &= (keys[at] == want) | (t < 0)[:, None]
    return np.where(ok, d, np.float32(np.inf))


def control(data: Data, asked: np.ndarray,
            k: int) -> tuple[np.ndarray, np.ndarray]:
    """The same truth one precision down: each squared difference
    rounded to bfloat16 (the vectors themselves, integers below 256, are
    exact in it) and summed in float32, ranked and served as such."""
    import ml_dtypes

    def bf16(xs, q):
        diff = xs.astype(np.float32) - q.astype(np.float32)[None, :]
        sq = (diff * diff).astype(ml_dtypes.bfloat16).astype(np.float32)
        return np.sum(sq, axis=1, dtype=np.float32)

    x, pool = data.corpus["vectors"], data.pool["queries"]
    ids = np.empty((len(asked), k), np.int64)
    dists = np.empty((len(asked), k), np.float32)
    for j, i in enumerate(asked):
        ids[j], dists[j] = _knn(x, pool[i], _qualifying(data, i), k, bf16)
    return ids, dists
