"""QPS-vs-memory-budget curve for the out-of-HBM streaming page tier.

The tentpole claim of the streaming tier is that a PageANN artifact much
larger than the device-resident budget still serves **bit-identical**
results: only the hottest pages (by the artifact's persisted
``page_order`` access counts) are pinned on device, the rest stream from
the ``pages.bin`` memmap through a per-hop host callback. This benchmark
quantifies what that costs: one saved artifact is reloaded under a
shrinking :class:`repro.core.MemoryBudget` and each point records

  * read throughput (QPS) and per-query latency of the batched search,
  * recall@10 against brute-force ground truth,
  * the resident/streamed split (``resident_pages`` / ``resident_bytes``
    vs ``disk_bytes``) and the host fetch counters
    (``pages_fetched`` / ``fetch_hits`` / ``fetch_wall_s``),
  * ``bit_identical`` — ids AND dists exactly equal to the fully
    resident baseline (hard-asserted; a mismatch fails the run).

Results land in ``BENCH_stream.json``.

  PYTHONPATH=src python -m benchmarks.stream [--out BENCH_stream.json]
      [--smoke]

``--smoke`` is the CI gate: a tiny index served at a 0.25x budget (~4x
larger than the resident region), with hard bit-identity and recall
assertions against the fully resident load.
"""
from __future__ import annotations

import argparse
import json
import platform
import tempfile
import time

import numpy as np

from repro.core import (
    MemoryBudget,
    MemoryMode,
    PageANNConfig,
    PageANNIndex,
    SearchParams,
    recall_at_k,
)
from repro.core.vamana import brute_force_knn
from repro.data.pipeline import clustered_vectors, query_vectors

K = 10
BUDGET_FRACTIONS = (1.0, 0.75, 0.5, 0.25, 0.125)


def _timeit(fn, repeats=3):
    import jax

    jax.block_until_ready(fn())  # compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = jax.block_until_ready(fn())
    return out, (time.perf_counter() - t0) / repeats


def _point(idx, res, dt, nq, truth, baseline, frac) -> dict:
    s = idx.stats
    fs = idx.fetch_stats()
    identical = bool(
        np.array_equal(np.asarray(res.ids), np.asarray(baseline.ids))
        and np.array_equal(np.asarray(res.dists), np.asarray(baseline.dists))
    )
    return dict(
        budget_fraction=frac,
        qps=nq / dt if dt > 0 else 0.0,
        us_per_query=1e6 * dt / nq,
        recall=recall_at_k(res.ids, truth),
        mean_ios=float(np.asarray(res.ios).mean()),
        resident_pages=s.resident_pages,
        total_pages=s.pages,
        resident_bytes=s.resident_bytes,
        disk_bytes=s.disk_bytes,
        bit_identical=identical,
        **fs,
    )


def sweep(artifact: str, queries: np.ndarray, truth: np.ndarray,
          params: SearchParams, fractions) -> list[dict]:
    """Load ``artifact`` at each budget and measure; the 1x point is the
    baseline every smaller budget must match bit for bit."""
    points = []
    baseline = None
    for frac in fractions:
        budget = None if frac >= 1.0 else MemoryBudget(fraction=frac)
        idx = PageANNIndex.load(artifact, memory_budget=budget)
        res, dt = _timeit(lambda: idx.search(queries, params=params))
        if baseline is None:
            baseline = res
        pt = _point(idx, res, dt, len(queries), truth, baseline, frac)
        points.append(pt)
        print(
            f"budget={frac:5.3f}x  qps={pt['qps']:8.1f}  "
            f"recall={pt['recall']:.4f}  "
            f"resident={pt['resident_pages']}/{pt['total_pages']} pages  "
            f"fetched={pt['pages_fetched']} (hits={pt['fetch_hits']})  "
            f"bit_identical={pt['bit_identical']}"
        )
        if not pt["bit_identical"]:
            raise SystemExit(
                f"STREAMING MISMATCH: budget {frac}x diverged from the "
                "fully resident baseline"
            )
    return points


def run(n: int, dim: int, q: int, cfg: PageANNConfig, fractions,
        directory: str) -> dict:
    x = clustered_vectors(n, dim, num_clusters=max(8, n // 125), seed=0)
    queries = query_vectors(x, q, seed=1)
    truth = brute_force_knn(x, queries, K)
    params = SearchParams.from_config(cfg)

    t0 = time.perf_counter()
    idx = PageANNIndex.build(x, cfg)
    build_s = time.perf_counter() - t0
    # warm so the saved page_order ranks pages by real access counts — the
    # hotness ordering every budgeted reload pins its resident region by
    idx.warm_cache(np.asarray(queries), params=params)
    idx.save(directory)

    points = sweep(directory, queries, truth, params, fractions)
    return dict(
        bench="stream",
        n=n, dim=dim, queries=q, k=K,
        build_s=build_s,
        page_record_bytes=idx.store.padded_tile_bytes(),
        platform=platform.platform(),
        points=points,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write BENCH_stream.json here")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny CI gate: serve a small index at a 0.25x memory budget "
             "with hard bit-identity + recall assertions",
    )
    args = ap.parse_args(argv)

    if args.smoke:
        cfg = PageANNConfig(
            dim=32, graph_degree=12, build_beam=24, pq_subspaces=8,
            lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
            memory_mode=MemoryMode.HYBRID,
        )
        with tempfile.TemporaryDirectory() as tmp:
            doc = run(n=1200, dim=32, q=16, cfg=cfg,
                      fractions=(1.0, 0.25), directory=tmp)
    else:
        from benchmarks import common

        cfg = common.base_cfg()
        x, queries, _ = common.dataset()
        artifact = common.index_cache_path("stream_art", cfg, x)
        from repro.core import persist

        if not persist.is_index_dir(artifact):
            idx = common.pageann_index(x, cfg, "stream")
            idx.warm_cache(
                np.asarray(queries), params=SearchParams.from_config(cfg)
            )
            idx.save(artifact)
        truth = brute_force_knn(x, queries, K)
        points = sweep(artifact, queries, truth,
                       SearchParams.from_config(cfg), BUDGET_FRACTIONS)
        doc = dict(
            bench="stream",
            n=common.N, dim=common.D, queries=common.Q, k=K,
            platform=platform.platform(),
            points=points,
        )

    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")

    if args.smoke:
        full, budgeted = doc["points"][0], doc["points"][-1]
        if not budgeted["bit_identical"]:
            raise SystemExit(
                "STREAM REGRESSION: budgeted results are not bit-identical "
                "to the fully resident load"
            )
        if budgeted["recall"] < full["recall"]:
            raise SystemExit(
                f"STREAM REGRESSION: budgeted recall {budgeted['recall']:.4f}"
                f" < resident {full['recall']:.4f}"
            )
        if budgeted["recall"] < 0.8:
            raise SystemExit(
                f"STREAM REGRESSION: recall {budgeted['recall']:.4f} < 0.8"
            )
        if not budgeted["resident_pages"] * 4 <= budgeted["total_pages"]:
            raise SystemExit(
                f"STREAM REGRESSION: budget not enforced — "
                f"{budgeted['resident_pages']}/{budgeted['total_pages']} "
                "pages resident at a 0.25x budget"
            )
        if budgeted["pages_fetched"] == 0:
            raise SystemExit(
                "STREAM REGRESSION: no host fetches at a 0.25x budget — "
                "the streaming path did not run"
            )
        print(
            f"stream smoke ok: {budgeted['resident_pages']}/"
            f"{budgeted['total_pages']} pages resident, "
            f"{budgeted['pages_fetched']} streamed fetches, results "
            f"bit-identical at recall {budgeted['recall']:.4f}"
        )


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
