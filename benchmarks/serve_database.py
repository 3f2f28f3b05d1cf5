"""Multi-collection serving: one VectorService vs N separate engines.

Builds N same-geometry collections (one config, one corpus size, distinct
data) and measures what the database-level API buys:

  * **marginal compile cost** — time-to-first-result per collection as it
    is added to one shared ``VectorService``. The first collection pays
    the jit compile for its geometry; every later same-geometry collection
    dispatches through the already-warm executable (the compile-cache
    hit/miss counters are recorded per step, and the expected shape is
    ``compile_misses_delta == 0`` from collection 1 on). The projected
    N-process cost — each process compiling its own executable — is
    ``N * first_collection_s`` and is reported alongside.
  * **steady-state throughput** — the same warm interleaved query stream
    (round-robin across collections) driven through the one service vs
    through N independent ``BatchingEngine.from_index`` instances, so the
    routing layer's overhead is visible (expected: parity — routing is a
    dict lookup, the searches are identical executables).
  * **recall@10 per collection** against brute force, and (``--smoke``)
    a hard bit-identity assertion: the service must return exactly what N
    independent engines return.

Results land in ``BENCH_multi.json``.

  PYTHONPATH=src python -m benchmarks.serve_database [--out BENCH_multi.json]
      [--smoke] [--collections N]

``--smoke`` is the CI gate: a tiny two-collection database, recall- and
bit-identity-gated, with a hard zero-marginal-compile assertion.
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

from repro.core import PageANNIndex, recall_at_k
from repro.core.vamana import brute_force_knn
from repro.data.pipeline import clustered_vectors, query_vectors
from repro.serve import BatchingEngine, VectorService

K = 10
ROUNDS = 3  # interleaved throughput rounds (min wall wins)


def _build_collections(c: int, n: int, dim: int, q: int, cfg):
    """C same-geometry corpora: one config, one size, distinct data."""
    cols = []
    for i in range(c):
        x = clustered_vectors(n, dim, num_clusters=max(8, n // 125), seed=i)
        queries = query_vectors(x, q, seed=100 + i)
        t0 = time.perf_counter()
        index = PageANNIndex.build(x, cfg)
        build_s = time.perf_counter() - t0
        cols.append(
            dict(
                name=f"c{i}", x=x, queries=queries, index=index,
                build_s=build_s, truth=brute_force_knn(x, queries, K),
            )
        )
    return cols


def _interleaved(submit_fn, cols, flush_fn) -> float:
    """Round-robin one query per collection until every stream drains;
    returns the wall seconds for the full interleave."""
    nq = len(cols[0]["queries"])
    t0 = time.perf_counter()
    futs = []
    for j in range(nq):
        for col in cols:
            futs.append(submit_fn(col["name"], col["queries"][j]))
    flush_fn()
    for f in futs:
        f.result()
    return time.perf_counter() - t0


def run(cols: list[dict], batch_size: int) -> dict:
    c = len(cols)
    n, dim = cols[0]["x"].shape
    q = len(cols[0]["queries"])
    nq_total = q * c

    # ---- one shared service: per-collection marginal cost as it grows
    svc = VectorService(batch_size=batch_size)
    points = []
    prev = svc.metrics()
    for col in cols:
        t0 = time.perf_counter()
        svc.create_collection(col["name"], col["index"], k=K)
        rows = svc.search(col["name"], col["queries"])
        first_result_s = time.perf_counter() - t0
        m = svc.metrics()
        ids = np.stack([r.result.ids for r in rows])
        points.append(
            dict(
                collection=col["name"],
                build_s=col["build_s"],
                first_result_s=first_result_s,
                compile_misses_delta=m.compile_misses - prev.compile_misses,
                compile_hits_delta=m.compile_hits - prev.compile_hits,
                recall=recall_at_k(ids, col["truth"]),
                mean_ios=float(np.mean([np.asarray(r.result.ios) for r in rows])),
            )
        )
        prev = m
        pt = points[-1]
        print(
            f"{pt['collection']}: first_result={pt['first_result_s']:.3f}s  "
            f"compile_misses+={pt['compile_misses_delta']}  "
            f"hits+={pt['compile_hits_delta']}  recall={pt['recall']:.4f}"
        )

    # ---- steady-state interleaved throughput: service vs N engines
    svc_wall = min(
        _interleaved(
            lambda name, qq: svc.submit(name, qq, k=K), cols, svc.flush
        )
        for _ in range(ROUNDS)
    )
    svc_metrics = svc.metrics()
    svc.close()

    engines = {
        col["name"]: BatchingEngine.from_index(
            col["index"], k=K, batch_size=batch_size
        )
        for col in cols
    }
    try:
        eng_wall = min(
            _interleaved(
                lambda name, qq: engines[name].submit(qq, k=K),
                cols,
                lambda: [e.flush() for e in engines.values()],
            )
            for _ in range(ROUNDS)
        )
    finally:
        for e in engines.values():
            e.close()

    doc = dict(
        bench="serve_database",
        collections=c, n=n, dim=dim, queries=q, k=K,
        batch_size=batch_size,
        platform=platform.platform(),
        points=points,
        service_qps=nq_total / svc_wall,
        engines_qps=nq_total / eng_wall,
        qps_ratio=eng_wall / svc_wall,
        # what N one-index-per-process deployments would pay in compile
        # wall vs what the shared-cache service actually paid
        projected_nprocess_first_result_s=c * points[0]["first_result_s"],
        service_first_result_s=sum(p["first_result_s"] for p in points),
        compile=dict(
            hits=svc_metrics.compile_hits,
            misses=svc_metrics.compile_misses,
            executables=svc_metrics.compiled_executables,
        ),
    )
    print(
        f"interleaved x{c} collections: service {doc['service_qps']:.1f} qps "
        f"vs {c} engines {doc['engines_qps']:.1f} qps "
        f"(ratio {doc['qps_ratio']:.2f})"
    )
    print(
        f"compile wall: shared-cache service {doc['service_first_result_s']:.2f}s "
        f"vs projected {c}-process {doc['projected_nprocess_first_result_s']:.2f}s"
    )
    return doc


def _bit_identity_check(cols: list[dict], batch_size: int):
    """Service results must be byte-for-byte what independent engines
    return — routing adds a key, never a different dispatch."""
    with VectorService(batch_size=batch_size) as svc:
        for col in cols:
            svc.create_collection(col["name"], col["index"], k=K)
        got = {
            col["name"]: svc.search(col["name"], col["queries"])
            for col in cols
        }
    for col in cols:
        with BatchingEngine.from_index(
            col["index"], k=K, batch_size=batch_size
        ) as eng:
            want = eng.search(col["queries"])
        for g, w in zip(got[col["name"]], want):
            for field in ("ids", "dists", "ios", "hops", "cache_hits"):
                a = np.asarray(getattr(g.result, field))
                b = np.asarray(getattr(w.result, field))
                assert np.array_equal(a, b), (col["name"], field)
    print(f"bit-identity: service == {len(cols)} independent engines, "
          "all fields")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write BENCH_multi.json here")
    ap.add_argument("--collections", type=int, default=None)
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny CI gate: two-collection database, recall floor, "
             "bit-identity vs independent engines, zero marginal compiles",
    )
    args = ap.parse_args(argv)

    if args.smoke:
        from repro.core import MemoryMode, PageANNConfig

        c = args.collections or 2
        cfg = PageANNConfig(
            dim=32, graph_degree=12, build_beam=24, pq_subspaces=8,
            lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
            memory_mode=MemoryMode.HYBRID,
        )
        # build the collections ONCE and share them between the throughput
        # run and the bit-identity check (same seeds -> same data anyway)
        cols = _build_collections(c, 900, 32, 16, cfg)
        doc = run(cols, batch_size=8)
        _bit_identity_check(cols, batch_size=8)
    else:
        from benchmarks import common

        c = args.collections or 3
        cols = _build_collections(
            c, common.N, common.D, common.Q, common.base_cfg()
        )
        doc = run(cols, batch_size=64)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")

    if args.smoke:
        for pt in doc["points"][1:]:
            if pt["compile_misses_delta"] != 0:
                raise SystemExit(
                    f"MULTI-COLLECTION REGRESSION: {pt['collection']} compiled "
                    f"{pt['compile_misses_delta']} new executables — same-"
                    "geometry collections must share the warm cache"
                )
        floor = doc["points"][0]["recall"] - 0.02
        for pt in doc["points"]:
            if pt["recall"] < floor:
                raise SystemExit(
                    f"MULTI-COLLECTION REGRESSION: {pt['collection']} recall "
                    f"{pt['recall']:.4f} < {floor:.4f}"
                )
        print(
            f"serve_database smoke ok: {doc['collections']} collections, "
            "0 marginal compiles, recall + bit-identity gates passed"
        )


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
