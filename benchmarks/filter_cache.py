"""Filtered search recall parity + semantic query cache throughput.

Two claims, one artifact (``BENCH_filter.json``):

1. **Recall parity** — enforcing a metadata predicate *inside* the page
   scan (filtered members scored ``+inf`` before the top-k merge, beam
   pow2-oversampled by measured selectivity) matches a post-filter brute
   force oracle across selectivities {0.5, 0.1, 0.01}, and
   ``filter=None`` stays bit-identical to an index built with no
   metadata at all. The filtered path is also checked bit-identical
   between the fully resident index and a save/load under a
   ``MemoryBudget`` (the PR-6 streamed tier).

2. **Semantic cache throughput** — a :class:`repro.serve.SemanticCache`
   in front of :class:`repro.serve.VectorService` on a Zipf-distributed
   query mix (repeat questions dominate, the RAG serving pattern) beats
   the uncached service by >= 2x QPS, and a write to the collection
   invalidates its cached answers.

Usage:
  PYTHONPATH=src python -m benchmarks.filter_cache --smoke --out BENCH_filter.json
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import (
    MemoryBudget,
    MemoryMode,
    MetadataSchema,
    MutableIndex,
    Num,
    PageANNConfig,
    PageANNIndex,
    recall_at_k,
)
from repro.data.pipeline import clustered_vectors, query_vectors
from repro.serve import SemanticCache, VectorService

K = 10
SELECTIVITIES = (0.5, 0.1, 0.01)
ZIPF_QUERIES = 400      # total requests in the cache mix
ZIPF_UNIQUE = 48        # distinct questions the mix draws from
ZIPF_EXPONENT = 1.1


# --------------------------------------------------------------- oracles
def filtered_truth(x: np.ndarray, q: np.ndarray, mask: np.ndarray, k: int):
    """Post-filter brute force: exact top-k restricted to passing rows."""
    idx = np.flatnonzero(mask)
    d = ((q[:, None, :] - x[idx][None]) ** 2).sum(-1)
    take = min(k, len(idx))
    order = np.argsort(d, axis=1)[:, :take]
    out = np.full((len(q), k), -1, np.int64)
    out[:, :take] = idx[order]
    return out


def results_equal(a, b) -> bool:
    return np.array_equal(np.asarray(a.ids), np.asarray(b.ids)) and np.array_equal(
        np.asarray(a.dists), np.asarray(b.dists)
    )


# --------------------------------------------------------- filter parity
def measure_filtered(idx, queries, expr, truth, label):
    idx.search(queries, K, filter=expr)  # compile
    t0 = time.perf_counter()
    res = idx.search(queries, K, filter=expr)
    dt = time.perf_counter() - t0
    rec = recall_at_k(res.ids, truth)
    _, sel = idx.compiled_filter(expr)
    return dict(
        label=label,
        selectivity=round(sel, 4),
        recall=round(float(rec), 4),
        us_per_query=round(dt / len(queries) * 1e6, 1),
    ), res


def run_filter_section(x, queries, cfg, tmpdir):
    """Parity rows + the two bit-identity gates. Returns (rows, ok)."""
    rng = np.random.default_rng(7)
    scores = rng.uniform(0.0, 1.0, len(x))
    schema = MetadataSchema(numerics=("score",))
    idx = PageANNIndex.build(x, cfg, schema=schema, metadata={"score": scores})

    rows, ok = [], True
    resident = {}
    for sel in SELECTIVITIES:
        thr = float(np.quantile(scores, sel))
        expr = Num("score").le(thr)
        mask = scores <= thr
        truth = filtered_truth(x, queries, mask, K)
        row, res = measure_filtered(idx, queries, expr, truth, f"sel={sel}")
        resident[sel] = (expr, res)
        ok = ok and row["recall"] >= 0.9
        rows.append(row)

    # filter=None must be bit-identical to an index built with no metadata
    plain = PageANNIndex.build(x, cfg)
    bit_ok = results_equal(idx.search(queries, K), plain.search(queries, K))
    ok = ok and bit_ok
    rows.append(dict(label="no_filter_bit_identity", passed=bool(bit_ok)))

    # streamed tier: save/load under a budget, filtered results identical
    import os

    d = os.path.join(tmpdir, "filter_bench.pageann")
    idx.save(d)
    streamed = PageANNIndex.load(d, memory_budget=MemoryBudget(fraction=0.25))
    stream_ok = all(
        results_equal(streamed.search(queries, K, filter=expr), res)
        for expr, res in resident.values()
    )
    ok = ok and stream_ok
    rows.append(dict(label="streamed_bit_identity", passed=bool(stream_ok)))
    return rows, ok


# ------------------------------------------------------------ cache mix
def zipf_mix(dim: int, x: np.ndarray, seed: int = 3):
    """A Zipf-distributed repeat-heavy query stream over a small pool of
    distinct questions — the shape a semantic cache is built for."""
    rng = np.random.default_rng(seed)
    pool = query_vectors(x, ZIPF_UNIQUE, seed=seed)
    ranks = rng.zipf(ZIPF_EXPONENT, size=ZIPF_QUERIES * 4)
    ranks = ranks[ranks <= ZIPF_UNIQUE][:ZIPF_QUERIES]
    while len(ranks) < ZIPF_QUERIES:  # zipf tail can overshoot the pool
        ranks = np.concatenate([ranks, ranks])[:ZIPF_QUERIES]
    return pool[ranks - 1]


def timed_qps(svc: VectorService, mix: np.ndarray) -> float:
    svc.search("docs", mix[:8])  # compile
    t0 = time.perf_counter()
    futs = [svc.submit("docs", q) for q in mix]
    svc.flush()
    for f in futs:
        f.result()
    return len(mix) / (time.perf_counter() - t0)


def run_cache_section(x, cfg):
    """QPS with/without the cache on the same Zipf mix + an invalidation
    check after a write. Returns (rows, ok)."""
    mix = zipf_mix(cfg.dim, x)
    base = PageANNIndex.build(x, cfg)

    with VectorService(batch_size=16) as svc:
        svc.create_collection("docs", MutableIndex(base), k=K)
        qps_plain = timed_qps(svc, mix)

    cache = SemanticCache(threshold=0.999)
    with VectorService(batch_size=16, semantic_cache=cache) as svc:
        svc.create_collection("docs", MutableIndex(base), k=K)
        qps_cached = timed_qps(svc, mix)
        m = svc.metrics()
        hits, misses = m.semantic_hits, m.semantic_misses

        # a write must invalidate: the hottest question re-asked after an
        # insert is a miss, not a stale hit
        hot = mix[0]
        svc.insert("docs", hot[None] + 0.5)
        fut = svc.submit("docs", hot)
        svc.flush()
        inval_ok = (not fut.result().cached) and (
            svc.metrics().semantic_invalidations > 0
        )

    speedup = qps_cached / max(qps_plain, 1e-9)
    ok = speedup >= 2.0 and inval_ok and hits > misses
    rows = [
        dict(
            label="semantic_cache_zipf",
            qps_uncached=round(qps_plain, 1),
            qps_cached=round(qps_cached, 1),
            speedup=round(speedup, 2),
            hits=hits,
            misses=misses,
            invalidation_ok=bool(inval_ok),
        )
    ]
    return rows, ok


# ------------------------------------------------------------- harness
def smoke_cfg() -> PageANNConfig:
    return PageANNConfig(
        dim=32, graph_degree=12, build_beam=24, pq_subspaces=8,
        lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
        memory_mode=MemoryMode.HYBRID,
    )


def run_smoke():
    import tempfile

    x = clustered_vectors(1200, 32, num_clusters=16, seed=0)
    queries = query_vectors(x, 16, seed=1)
    with tempfile.TemporaryDirectory() as tmp:
        filter_rows, filter_ok = run_filter_section(x, queries, smoke_cfg(), tmp)
    cache_rows, cache_ok = run_cache_section(x, smoke_cfg())
    return filter_rows + cache_rows, filter_ok, cache_ok


def run_full():
    import tempfile

    import repro.core.vamana as vam
    from benchmarks import common

    x, queries, _ = common.dataset()
    cfg = common.base_cfg()
    # vamana dominates build time and is metadata-independent: share the
    # harness's cached graph across the three builds here
    nbrs = common.vamana_graph(x)
    orig = vam.build_vamana
    vam.build_vamana = lambda *a, **k: nbrs
    try:
        with tempfile.TemporaryDirectory() as tmp:
            filter_rows, filter_ok = run_filter_section(x, queries, cfg, tmp)
        cache_rows, cache_ok = run_cache_section(x, cfg)
    finally:
        vam.build_vamana = orig
    return filter_rows + cache_rows, filter_ok, cache_ok


def run(out: str = "BENCH_filter.json"):
    """Harness entry (benchmarks.run): full dataset, returns row strings."""
    rows, filter_ok, cache_ok = run_full()
    doc = dict(rows=rows, filter_ok=filter_ok, cache_ok=cache_ok)
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
    lines = []
    for r in rows:
        us = r.get("us_per_query", 0.0)
        detail = ";".join(f"{k}={v}" for k, v in r.items() if k != "label")
        lines.append(f"filter_{r['label']},{us},{detail}")
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_filter.json")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)

    rows, filter_ok, cache_ok = run_smoke() if args.smoke else run_full()
    doc = dict(
        mode="smoke" if args.smoke else "full",
        rows=rows,
        filter_ok=filter_ok,
        cache_ok=cache_ok,
    )
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
    for r in rows:
        print(r)
    print(f"wrote {args.out}")

    if args.smoke:
        if not filter_ok:
            raise SystemExit(
                "FILTER REGRESSION: filtered recall below parity or "
                "bit-identity gate failed (see rows above)"
            )
        if not cache_ok:
            raise SystemExit(
                "CACHE REGRESSION: semantic cache speedup < 2x or "
                "invalidation failed (see rows above)"
            )
        print("smoke gates passed: recall parity, bit identity, cache >=2x")


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
