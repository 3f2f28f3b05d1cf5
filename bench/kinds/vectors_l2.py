"""Kind ``vectors_l2``: float32 vectors, served and judged under squared L2.

The big-ann-benchmarks T1 shape: a corpus of clustered Gaussian vectors,
queries that are corpus points plus noise, one ``PageANNIndex`` built
with the configuration's ``index`` settings, requests that carry ``k``
and the query vectors alone, and exact squared-L2 kNN
(bench/reference.py) as the truth. bench/spec.py sets out what each
function of a kind gives. The corpus follows the clustered-Gaussian
model of ``repro.data.pipeline.clustered_vectors`` (SIFT-like local
structure), written out here so that no change to the program can change
the data it is measured on. This module imports numpy only at its top:
the load generator, which never imports JAX, loads it for ``encoded``
and ``body``.
"""
from __future__ import annotations

import json

import numpy as np

from bench import reference
from bench.corpus import BASE_SEED, CORPUS, POOL, Data, rng


def make_corpus(cfg: dict) -> np.ndarray:
    """(N, dim) float32: ``clusters`` Gaussian centres, each point one
    centre plus ``cluster_scale`` times standard normal noise."""
    r = rng(BASE_SEED, CORPUS)
    n, dim, c = cfg["num_vectors"], cfg["dim"], cfg["clusters"]
    centres = r.standard_normal((c, dim), dtype=np.float32)
    assign = r.integers(0, c, n)
    noise = r.standard_normal((n, dim), dtype=np.float32)
    return np.ascontiguousarray(
        centres[assign] + np.float32(cfg["cluster_scale"]) * noise
    )


def make_pool(x: np.ndarray, mix: dict) -> np.ndarray:
    """(pool, dim) float32 queries: corpus points plus ``query_noise``
    times standard normal noise."""
    r = rng(BASE_SEED, POOL)
    base = x[r.integers(0, len(x), mix["pool"])]
    noise = r.standard_normal(base.shape, dtype=np.float32)
    return np.ascontiguousarray(base + np.float32(mix["query_noise"]) * noise)


def data(cfg: dict, mix: dict) -> Data:
    x = make_corpus(cfg)
    return Data({"vectors": x}, {"queries": make_pool(x, mix)})


def digest_inputs(cfg: dict) -> dict:
    return {"corpus": {k: cfg[k] for k in
                       ("num_vectors", "dim", "clusters", "cluster_scale")},
            "index": cfg["index"]}


def build(cfg: dict, data: Data, path: str) -> None:
    from repro.core import MemoryMode, PageANNConfig, PageANNIndex

    ix = cfg["index"]
    PageANNIndex.build(data.corpus["vectors"], PageANNConfig(
        dim=cfg["dim"], graph_degree=ix["graph_degree"],
        build_beam=ix["build_beam"], pq_subspaces=ix["pq_subspaces"],
        page_bytes=ix["page_bytes"], lsh_sample=ix["lsh_sample"],
        seed=ix["seed"], memory_mode=MemoryMode(ix["memory_mode"]),
    )).save(path)


def attach(svc, collection: str, path: str, cfg: dict, k: int,
           memory_budget) -> None:
    from repro.core import SearchParams

    svc.attach(collection, path, params=SearchParams(k=k, **cfg["search"]),
               memory_budget=memory_budget)


def record_bytes(index) -> int:
    recs = index.data.page_recs
    return int(np.prod(recs.shape[1:])) * recs.dtype.itemsize


def encoded(pool: dict) -> list[str]:
    return [json.dumps(row) for row in pool["queries"].tolist()]


def body(s: dict, frags: list[str], qidx: np.ndarray) -> bytes:
    head = f'{{"collection": {json.dumps(s["collection"])}, "k": {s["k"]}, '
    if len(qidx) == 1:
        return (head + f'"query": {frags[qidx[0]]}}}').encode()
    return (head + '"queries": [' + ", ".join(frags[i] for i in qidx)
            + "]}").encode()


def truth(data: Data, asked: np.ndarray,
          k: int) -> tuple[np.ndarray, np.ndarray]:
    return reference.exact_knn(data.corpus["vectors"],
                               data.pool["queries"][asked], k)


def distances(data: Data, qidx: np.ndarray, ids: np.ndarray) -> np.ndarray:
    return reference.sq_dists(data.corpus["vectors"],
                              data.pool["queries"][qidx], ids)


def control(data: Data, asked: np.ndarray,
            k: int) -> tuple[np.ndarray, np.ndarray]:
    return reference.control_knn(data.corpus["vectors"],
                                 data.pool["queries"][asked], k)
