"""Paper Figs. 7/8 + Table 3: latency / throughput / mean I/Os vs recall@10.

Sweeps the runtime search knobs (beam L, LSH top-T) for PageANN and both
baselines; reports the full curve plus the Table-3-style comparison at
recall >= 0.9. All three systems are driven through the same
``VectorIndex`` protocol (``search(queries, k, params)``), and the PageANN
sweep runs over ONE built index: each point is a per-call ``SearchParams``
(a fresh jit executable, not a fresh index). The sweep wall-clock is
recorded both ways — build-once (measured) vs rebuild-per-point (what the
pre-lifecycle API paid, estimated from the measured single acquisition) —
into ``BENCH_recall_io.json`` so the API win is a tracked number.

Wall-clock QPS on this CPU container is a *relative* proxy (all three run
the same JAX/XLA substrate); the architecture-level metric is mean I/Os
per query.
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

from benchmarks import common
from repro.core import AdaptiveParams, SearchParams, recall_at_k
from repro.core import baselines as bl

# (beam L, LSH top-T) sweep — the paper's recall axis
PAGEANN_SWEEP = ((16, 4), (32, 8), (64, 12), (96, 16), (128, 24))
BASELINE_BEAMS = (16, 32, 64, 96, 128)


def _sweep_index(idx, q, truth, system: str, points) -> list[dict]:
    """One built index, one protocol, many ``SearchParams`` — no rebuilds."""
    out = []
    for params in points:
        res, dt = common.timeit(lambda: idx.search(q, params=params))
        out.append(
            dict(system=system, beam=params.beam_width,
                 entries=params.lsh_entries,
                 recall=recall_at_k(np.asarray(res.ids), truth),
                 ios=float(np.asarray(res.ios).mean()), qps=len(q) / dt,
                 ms=1000 * dt / len(q))
        )
    return out


def _curve_pageann(x, q, truth) -> tuple[list[dict], dict]:
    cfg = common.base_cfg()
    idx, acquired, acquire_s = common.pageann_index_timed(x, cfg, "recall_io")

    points = [
        SearchParams(k=10, beam_width=beam, io_batch=cfg.io_batch,
                     max_hops=cfg.max_hops, lsh_entries=entries)
        for beam, entries in PAGEANN_SWEEP
    ]
    t1 = time.perf_counter()
    curve = _sweep_index(idx, q, truth, "pageann", points)
    search_s = time.perf_counter() - t1
    timing = dict(
        acquired=acquired,              # "build" (cold cache) or "load"
        acquire_s=acquire_s,
        search_sweep_s=search_s,
        points=len(points),
        # the lifecycle-API workflow: one acquisition, N SearchParams
        build_once_wall_s=acquire_s + search_s,
    )
    if acquired == "build":
        # what the pre-SearchParams API paid: one full build per point
        # (only meaningful when this run actually measured a fresh build)
        timing["rebuild_per_point_wall_s_est"] = (
            len(points) * acquire_s + search_s
        )

    # adaptive rows over the SAME built index: hand-picked defaults vs the
    # same knobs with early termination vs the autotuned operating point —
    # the I/O-reduction claim of the adaptive engine as a tracked number
    from repro.data.pipeline import query_vectors

    hand = SearchParams.from_config(cfg)
    et = hand.replace(adaptive=AdaptiveParams(patience=2))
    adaptive = _sweep_index(idx, q, truth, "pageann_hand", [hand])
    adaptive += _sweep_index(idx, q, truth, "pageann_early_term", [et])
    tuned = idx.autotune(
        np.asarray(query_vectors(x, len(q), seed=2)),
        recall_target=0.95, k=10, patience_grid=(None, 2, 4),
    )["params"]
    adaptive += _sweep_index(idx, q, truth, "pageann_autotuned", [tuned])
    return curve + adaptive, timing


def _curve_baseline(x, q, truth, style: str) -> list[dict]:
    nbrs, books = common.baseline_data(x)
    if style == "starling":
        from repro.core.page_graph import group_pages

        cap = common.base_cfg().resolve_capacity()
        g = group_pages(x, nbrs, capacity=cap, h=2)
        idx = bl.StarlingIndex.from_data(x, nbrs, books, page_of=g.page_of)
    else:
        idx = bl.DiskANNIndex.from_data(x, nbrs, books)
    points = [
        SearchParams(k=10, beam_width=beam, max_hops=64)
        for beam in BASELINE_BEAMS
    ]
    return _sweep_index(idx, q, truth, style, points)


def _at_recall(curve, target=0.9):
    ok = [c for c in curve if c["recall"] >= target]
    return min(ok, key=lambda c: c["ios"]) if ok else None


def run(out: str | None = "BENCH_recall_io.json") -> list[str]:
    x, q, truth = common.dataset()
    pageann_curve, timing = _curve_pageann(x, q, truth)
    curves = (
        pageann_curve
        + _curve_baseline(x, q, truth, "diskann")
        + _curve_baseline(x, q, truth, "starling")
    )
    rows = []
    for c in curves:
        rows.append(
            f"recall_io_{c['system']}_beam{c['beam']},{1e6 * c['ms'] / 1000:.1f},"
            f"recall={c['recall']:.3f};ios={c['ios']:.1f};qps={c['qps']:.0f}"
        )
    est = timing.get("rebuild_per_point_wall_s_est")
    rows.append(
        f"recall_io_sweep_wall,{1e6 * timing['build_once_wall_s']:.0f},"
        f"acquired={timing['acquired']};"
        f"build_once_s={timing['build_once_wall_s']:.2f}"
        + (f";rebuild_per_point_s_est={est:.2f}" if est is not None else "")
    )
    # Table 3 analog at recall@10 >= 0.9
    best = {
        s: _at_recall([c for c in curves if c["system"] == s])
        for s in ("pageann", "diskann", "starling")
    }
    if all(best.values()):
        p, d, s = best["pageann"], best["diskann"], best["starling"]
        second = min(d, s, key=lambda c: c["ios"])
        rows.append(
            f"table3_at_r90,0.0,pageann_ios={p['ios']:.1f};second_best_ios={second['ios']:.1f};"
            f"io_reduction={100 * (1 - p['ios'] / second['ios']):.1f}%;"
            f"pageann_qps={p['qps']:.0f};diskann_qps={d['qps']:.0f};starling_qps={s['qps']:.0f}"
        )
    if out:
        doc = dict(
            bench="recall_io",
            n=common.N,
            dim=common.D,
            queries=common.Q,
            platform=platform.platform(),
            sweep_timing=timing,
            curves=curves,
        )
        with open(out, "w") as f:
            json.dump(doc, f, indent=2)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_recall_io.json")
    args = ap.parse_args(argv)
    for r in run(out=args.out):
        print(r)


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
