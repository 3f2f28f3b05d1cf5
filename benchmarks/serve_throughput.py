"""Serving-path throughput: the batching engine swept over batch sizes.

Drives the request stream of ``repro.serve.BatchingEngine`` (one query per
``submit``, fixed-shape dispatch, demux) at batch sizes 1/8/64 and reports
QPS, p50/p99 request latency, and mean disk page reads per query — the
serving analogue of the paper's Fig. 7 throughput axis. ``main`` records the
sweep to BENCH_serve.json so later PRs have a perf trajectory to beat.

  PYTHONPATH=src python -m benchmarks.serve_throughput [--out BENCH_serve.json]
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

from benchmarks import common
from repro.core import recall_at_k
from repro.serve import BatchingEngine

BATCH_SIZES = (1, 8, 64)
K = 10


def _drive(index, queries: np.ndarray, batch_size: int) -> dict:
    """Stream every query through a fresh engine; return the sweep point."""
    # warm the jit cache so compile time doesn't pollute the latency stats
    with BatchingEngine.from_index(index, k=K, batch_size=batch_size) as warm:
        warm.search(queries[:batch_size])

    with BatchingEngine.from_index(index, k=K, batch_size=batch_size) as engine:
        t0 = time.perf_counter()
        futures = [engine.submit(q) for q in queries]
        engine.flush()
        rows = [f.result() for f in futures]
        wall = time.perf_counter() - t0
        m = engine.metrics()
    ids = np.stack([r.result.ids for r in rows])
    return dict(
        batch_size=batch_size,
        qps=len(queries) / wall,
        p50_ms=m.latency_ms_p50,
        p99_ms=m.latency_ms_p99,
        mean_ios=m.mean_ios,
        batches=m.batches,
        occupancy=m.mean_batch_occupancy,
        _ids=ids,
    )


def sweep(batch_sizes=BATCH_SIZES) -> list[dict]:
    x, q, truth = common.dataset()
    index = common.pageann_index(x, common.base_cfg(), "serve")
    points = []
    for bs in batch_sizes:
        pt = _drive(index, q, bs)
        pt["recall"] = recall_at_k(pt.pop("_ids"), truth)
        points.append(pt)
    return points


def run() -> list[str]:
    rows = []
    for pt in sweep():
        rows.append(
            f"serve_batch{pt['batch_size']},{1e3 * pt['p50_ms']:.1f},"
            f"qps={pt['qps']:.0f};p99_ms={pt['p99_ms']:.1f};"
            f"ios={pt['mean_ios']:.1f};recall={pt['recall']:.3f}"
        )
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write BENCH_serve.json here")
    args = ap.parse_args(argv)
    points = sweep()
    for pt in points:
        print(
            f"batch={pt['batch_size']:3d}  qps={pt['qps']:8.1f}  "
            f"p50={pt['p50_ms']:7.2f}ms  p99={pt['p99_ms']:7.2f}ms  "
            f"ios={pt['mean_ios']:5.1f}  recall={pt['recall']:.3f}"
        )
    if args.out:
        doc = dict(
            bench="serve_throughput",
            n=common.N,
            dim=common.D,
            queries=common.Q,
            k=K,
            platform=platform.platform(),
            points=points,
        )
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
