"""Adaptive query engine benchmark: what query-sensitive search buys.

Three claims from the adaptive engine (PR 7), measured over ONE built
index on a **skewed query mix** — half "easy" queries (duplicates of base
vectors: the index finds them in a handful of hops) and half "hard"
held-out queries (the long tail that needs the full traversal):

  * **Early termination** (``AdaptiveParams.patience``): easy queries
    exit the hop loop when their top-k stops improving instead of running
    until the beam is exhausted — mean hops and mean I/Os drop while
    recall stays within a hair of the non-adaptive run.
  * **Entry selection** (``entry_slack_bits``): confidently-routed
    queries seed the beam only with entry candidates within a Hamming
    slack of their best hit, scheduling fewer junk pages up front.
  * **Autotuning** (``PageANNIndex.autotune``): given only a recall
    target, the binary-searched operating point lands within a few
    percent of the best QPS an exhaustive grid search finds at that
    recall — nobody hand-picks beam/patience again.

Each row records params / recall / QPS / mean+p99 hops / mean I/Os; the
autotune section additionally records the grid-search optimum it is
judged against. Results land in ``BENCH_adaptive.json``.

  PYTHONPATH=src python -m benchmarks.adaptive [--out BENCH_adaptive.json]
      [--smoke]

``--smoke`` is the CI gate: a tiny index, hard-asserting that
(a) results with adaptive features disabled — both ``adaptive=None`` and
an all-default ``AdaptiveParams()`` — are **bit-identical** to the
pre-adaptive loop on every ``SearchResult`` field, and (b) the autotuned
operating point actually meets its recall floor.
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

from repro.core import (
    AdaptiveParams,
    MemoryMode,
    PageANNConfig,
    PageANNIndex,
    SearchParams,
    recall_at_k,
)
from repro.core.vamana import brute_force_knn
from repro.data.pipeline import clustered_vectors, query_vectors

K = 10
RECALL_TARGET = 0.95
# autotuned QPS must land within this factor of the grid-search optimum
AUTOTUNE_QPS_SLACK = 0.90


def skewed_mix(x: np.ndarray, queries: np.ndarray, n_each: int, seed: int = 3):
    """Half duplicates of base vectors (easy), half held-out (hard)."""
    rng = np.random.default_rng(seed)
    easy = x[rng.choice(len(x), n_each, replace=False)]
    hard = np.asarray(queries)[:n_each]
    return np.concatenate([easy, hard]), n_each


def measure(idx: PageANNIndex, mix: np.ndarray, n_easy: int,
            truth: np.ndarray, params: SearchParams, label: str) -> dict:
    import jax

    jax.block_until_ready(idx.search(mix, params=params).dists)  # compile
    t0 = time.perf_counter()
    res = idx.search(mix, params=params)
    jax.block_until_ready(res.dists)
    dt = time.perf_counter() - t0
    hops = np.asarray(res.hops)
    ios = np.asarray(res.ios)
    return dict(
        label=label,
        params=params.to_json(),
        recall=recall_at_k(res.ids, truth),
        qps=len(mix) / dt if dt > 0 else 0.0,
        us_per_query=1e6 * dt / len(mix),
        mean_hops=float(hops.mean()),
        p99_hops=float(np.percentile(hops, 99)),
        mean_hops_easy=float(hops[:n_easy].mean()),
        mean_hops_hard=float(hops[n_easy:].mean()),
        mean_ios=float(ios.mean()),
        mean_ios_easy=float(ios[:n_easy].mean()),
    )


def adaptive_rows(idx: PageANNIndex, x: np.ndarray, queries: np.ndarray,
                  cfg: PageANNConfig, n_each: int) -> list[dict]:
    """Hand-picked vs progressively adaptive rows over the same index."""
    mix, n_easy = skewed_mix(x, queries, n_each)
    truth = brute_force_knn(x, mix, K)
    base = SearchParams.from_config(cfg)
    rows = []
    for label, p in (
        ("hand-picked", base),
        ("early-termination", base.replace(adaptive=AdaptiveParams(patience=2))),
        ("entry+termination", base.replace(adaptive=AdaptiveParams(
            patience=2, entry_slack_bits=4, min_entries=4))),
    ):
        row = measure(idx, mix, n_easy, truth, p, label)
        rows.append(row)
        print(
            f"{label:18s} recall={row['recall']:.4f} "
            f"qps={row['qps']:8.1f} hops={row['mean_hops']:6.2f} "
            f"(easy {row['mean_hops_easy']:5.2f} / hard "
            f"{row['mean_hops_hard']:5.2f}) ios={row['mean_ios']:6.2f}"
        )
    return rows


def autotune_section(idx: PageANNIndex, x: np.ndarray, queries: np.ndarray,
                     cfg: PageANNConfig, n_each: int,
                     recall_target: float) -> dict:
    """Autotune on held-out tune queries, judge on the eval mix, and
    compare against an exhaustive grid search at the same target."""
    tune_q = query_vectors(x, max(16, n_each), seed=2)
    win = idx.autotune(
        np.asarray(tune_q), recall_target=recall_target, k=K,
        patience_grid=(None, 2, 4),
        entries_grid=(max(4, cfg.lsh_entries // 2),),
    )
    mix, n_easy = skewed_mix(x, queries, n_each)
    truth = brute_force_knn(x, mix, K)
    tuned_row = measure(idx, mix, n_easy, truth, win["params"], "autotuned")

    # exhaustive grid at the same target: the optimum autotune is judged by
    base = SearchParams.from_config(cfg, k=K)
    grid = []
    for bw in sorted({max(cfg.lsh_entries, cfg.beam_width // 4),
                      max(cfg.lsh_entries, cfg.beam_width // 2),
                      cfg.beam_width, 2 * cfg.beam_width}):
        for pat in (None, 2, 4):
            a = None if pat is None else AdaptiveParams(patience=pat)
            p = base.replace(beam_width=bw, adaptive=a)
            grid.append(measure(idx, mix, n_easy, truth, p,
                                f"grid:bw={bw},pat={pat}"))
    ok = [g for g in grid if g["recall"] >= recall_target]
    optimum = max(ok or grid, key=lambda g: g["qps"])
    print(
        f"autotuned          recall={tuned_row['recall']:.4f} "
        f"qps={tuned_row['qps']:8.1f}  (grid optimum {optimum['label']}: "
        f"recall={optimum['recall']:.4f} qps={optimum['qps']:8.1f})"
    )
    return dict(
        recall_target=recall_target,
        tuned=tuned_row,
        tuned_point={k: v for k, v in win.items() if k != "params"}
        | {"params": win["params"].to_json()},
        grid=grid,
        grid_optimum=optimum,
        qps_vs_optimum=(
            tuned_row["qps"] / optimum["qps"] if optimum["qps"] else 0.0
        ),
    )


def bit_identity_check(idx: PageANNIndex, queries: np.ndarray,
                       params: SearchParams) -> None:
    """Disabled adaptive features must change NOTHING: adaptive=None and
    an all-default AdaptiveParams() produce equal ids/dists/ios/hops/
    cache_hits."""
    want = idx.search(queries, params=params.replace(adaptive=None))
    got = idx.search(queries, params=params.replace(adaptive=AdaptiveParams()))
    for field in want._fields:
        if not np.array_equal(np.asarray(getattr(want, field)),
                              np.asarray(getattr(got, field))):
            raise SystemExit(
                f"ADAPTIVE REGRESSION: disabled-mode SearchResult.{field} "
                "is not bit-identical to the non-adaptive loop"
            )


def run_smoke() -> dict:
    cfg = PageANNConfig(
        dim=32, graph_degree=12, build_beam=24, pq_subspaces=8,
        lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
        memory_mode=MemoryMode.HYBRID,
    )
    x = clustered_vectors(1200, 32, num_clusters=16, seed=0)
    queries = query_vectors(x, 16, seed=1)
    idx = PageANNIndex.build(x, cfg)
    bit_identity_check(idx, queries, SearchParams.from_config(cfg))
    print("disabled-mode bit-identity: ok")
    rows = adaptive_rows(idx, x, queries, cfg, n_each=16)
    tuned = autotune_section(idx, x, queries, cfg, n_each=16,
                             recall_target=0.9)
    return dict(
        bench="adaptive", smoke=True,
        n=1200, dim=32, k=K,
        platform=platform.platform(),
        rows=rows, autotune=tuned,
    )


def run_full() -> dict:
    from benchmarks import common

    cfg = common.base_cfg()
    x, queries, _ = common.dataset()
    idx, acquired, acq_s = common.pageann_index_timed(x, cfg, "adaptive")
    print(f"index: {acquired} in {acq_s:.1f}s")
    bit_identity_check(idx, np.asarray(queries)[:16],
                       SearchParams.from_config(cfg))
    print("disabled-mode bit-identity: ok")
    rows = adaptive_rows(idx, x, queries, cfg, n_each=32)
    tuned = autotune_section(idx, x, queries, cfg, n_each=32,
                             recall_target=RECALL_TARGET)
    return dict(
        bench="adaptive",
        n=common.N, dim=common.D, k=K,
        platform=platform.platform(),
        rows=rows, autotune=tuned,
    )


def run(out: str | None = "BENCH_adaptive.json") -> list[str]:
    """Harness entry (``benchmarks.run``): full bench, CSV-ish rows."""
    doc = run_full()
    if out:
        with open(out, "w") as f:
            json.dump(doc, f, indent=2)
    rows = [
        f"adaptive_{r['label'].replace('-', '_').replace('+', '_')},"
        f"{r['us_per_query']:.1f},"
        f"recall={r['recall']:.3f};hops={r['mean_hops']:.2f};"
        f"ios={r['mean_ios']:.1f};qps={r['qps']:.0f}"
        for r in doc["rows"]
    ]
    t = doc["autotune"]
    rows.append(
        f"adaptive_autotuned,{t['tuned']['us_per_query']:.1f},"
        f"recall={t['tuned']['recall']:.3f};qps={t['tuned']['qps']:.0f};"
        f"grid_optimum_qps={t['grid_optimum']['qps']:.0f};"
        f"qps_vs_optimum={t['qps_vs_optimum']:.2f}"
    )
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="write BENCH_adaptive.json here")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny CI gate: disabled-mode bit-identity + tuned-params "
             "recall floor",
    )
    args = ap.parse_args(argv)

    doc = run_smoke() if args.smoke else run_full()

    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")

    # gates (hard in --smoke, reported otherwise): adaptive rows must not
    # give up recall, and the tuned point must meet its floor
    base = doc["rows"][0]
    et = doc["rows"][1]
    tuned = doc["autotune"]["tuned"]
    target = doc["autotune"]["recall_target"]
    if args.smoke:
        if et["recall"] < base["recall"] - 0.002:
            raise SystemExit(
                f"ADAPTIVE REGRESSION: early-termination recall "
                f"{et['recall']:.4f} dropped more than 0.002 below "
                f"hand-picked {base['recall']:.4f}"
            )
        if et["mean_hops"] > base["mean_hops"]:
            raise SystemExit(
                f"ADAPTIVE REGRESSION: early termination did not reduce "
                f"mean hops ({et['mean_hops']:.2f} vs "
                f"{base['mean_hops']:.2f})"
            )
        if tuned["recall"] < target - 0.02:
            raise SystemExit(
                f"ADAPTIVE REGRESSION: tuned operating point recall "
                f"{tuned['recall']:.4f} misses its target {target} by "
                "more than 0.02 on the eval mix"
            )
        print(
            f"adaptive smoke ok: bit-identical when disabled; "
            f"ET hops {base['mean_hops']:.2f}->{et['mean_hops']:.2f} at "
            f"recall {et['recall']:.4f}; tuned point recall "
            f"{tuned['recall']:.4f} (target {target})"
        )


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
