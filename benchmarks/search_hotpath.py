"""Search hot-path microbenchmark: the fused page-scan / top-k hop body.

Times the raw jitted ``core.search.batch_search`` (no serving-engine
overhead) on the exact BENCH_serve.json workload — same dataset, index
config, and 64-query set — at batch sizes 1/8/64, and reports QPS, per-hop
latency (batch wall time / while_loop iterations executed, i.e. the max hop
count in the batch), mean disk I/Os, and recall@10. ``main`` records the
sweep to BENCH_search.json next to the serving baseline's numbers so the
fused-kernel/top-k rewrite's speedup is a tracked artifact.

``--check BENCH_serve.json`` turns the run into a regression gate: the
optimized loop must reproduce the recorded mean I/Os exactly and must not
lose recall — the hop body is a speedup, not a semantic change. The gate
additionally proves request tracing (``repro.obs``) stays off the hot
path: the serving engine is run plain, with a disabled tracer, and with
an enabled tracer over the same workload — all three must return
bit-identical ids AND distances (the tracer never touches the compiled
program), and the enabled-tracer min-of-rounds wall must stay within 3%
of untraced.

  PYTHONPATH=src python -m benchmarks.search_hotpath \
      [--out BENCH_search.json] [--check BENCH_serve.json]
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import recall_at_k
from repro.core import search as search_mod

BATCH_SIZES = (1, 8, 64)
K = 10
ROUNDS = 7  # timed passes over the query set; min is reported (timeit style)


def _run_batches(index, queries: np.ndarray, batch_size: int):
    """Dispatch the query set through batch_search in batch_size chunks."""
    params = index.default_params.replace(k=K)
    chunks = [
        jnp.asarray(queries[i:i + batch_size], jnp.float32)
        for i in range(0, len(queries), batch_size)
    ]
    results = [
        jax.block_until_ready(
            search_mod.batch_search(
                c, index.data, params,
                capacity=index.store.capacity,
                mode=index.cfg.memory_mode.value,
            )
        )
        for c in chunks
    ]
    return results


def _measure(index, queries: np.ndarray, batch_size: int) -> dict:
    """Time ROUNDS full passes; report the fastest (the ``timeit`` min
    convention — this container's shared CPU adds ±20% scheduler noise to
    individual rounds, and the minimum is the stable estimate of what the
    code actually costs) plus the median for context."""
    results = _run_batches(index, queries, batch_size)  # compile + warm
    walls = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        results = _run_batches(index, queries, batch_size)
        walls.append(time.perf_counter() - t0)
    wall = min(walls)
    wall_median = sorted(walls)[len(walls) // 2]

    ids = np.concatenate([np.asarray(r.ids) for r in results])
    ios = np.concatenate([np.asarray(r.ios) for r in results])
    hops = np.concatenate([np.asarray(r.hops) for r in results])
    # a vmapped while_loop runs until the slowest lane finishes, so the
    # iteration count per dispatch is that dispatch's max hop count
    loop_iters = sum(
        int(np.asarray(r.hops).max()) for r in results
    )
    return dict(
        batch_size=batch_size,
        qps=len(queries) / wall,
        qps_median=len(queries) / wall_median,
        per_hop_ms=1e3 * wall / loop_iters,
        mean_hops=float(hops.mean()),
        mean_ios=float(ios.mean()),
        _ids=ids,
    )


def sweep(batch_sizes=BATCH_SIZES) -> list[dict]:
    x, q, truth = common.dataset()
    index = common.pageann_index(x, common.base_cfg(), "serve")
    points = []
    for bs in batch_sizes:
        pt = _measure(index, q, bs)
        pt["recall"] = recall_at_k(
            index.translate_ids(pt.pop("_ids")), truth
        )
        points.append(pt)
    return points


def tracing_gate(max_overhead: float = 0.03) -> dict:
    """Prove request tracing stays off the hot path.

    Runs the BENCH_serve workload through a ``BatchingEngine`` three ways
    — no tracer, ``Tracer(enabled=False)``, ``Tracer(enabled=True)`` —
    and (a) asserts all three return bit-identical ids and distances
    (tracing never changes the compiled program or the dispatch order),
    (b) measures the enabled-mode wall overhead and gates it at
    ``max_overhead``. The estimator is the median over rounds of the
    *paired* within-round ratio ``on / min(plain, off)`` — all three
    modes run back-to-back inside each round, so shared-CPU scheduler
    drift hits them equally and cancels in the ratio (a min-of-rounds
    difference across sequential runs swings ±3% on this container,
    swamping the ~0.6% true span-recording cost).
    Returns the measurement dict; raises AssertionError on divergence.
    """
    from repro.obs import Tracer
    from repro.serve import BatchingEngine

    x, q, _truth = common.dataset()
    index = common.pageann_index(x, common.base_cfg(), "serve")

    tr = Tracer()
    engines = {
        "plain": BatchingEngine.from_index(index, k=K, batch_size=64),
        "off": BatchingEngine.from_index(
            index, k=K, batch_size=64, tracer=Tracer(enabled=False)
        ),
        "on": BatchingEngine.from_index(
            index, k=K, batch_size=64, tracer=tr
        ),
    }
    results, walls = {}, {name: [] for name in engines}
    try:
        for eng in engines.values():
            eng.search(q)  # compile + warm
        # interleave the timed rounds so slow scheduler drift on a shared
        # CPU hits every mode equally instead of biasing whichever ran last
        for _ in range(max(ROUNDS, 11)):
            for name, eng in engines.items():
                t0 = time.perf_counter()
                rows = eng.search(q)
                walls[name].append(time.perf_counter() - t0)
                results[name] = rows
    finally:
        for eng in engines.values():
            eng.close()

    def arrays(rows):
        return (
            np.stack([np.asarray(r.result.ids) for r in rows]),
            np.stack([np.asarray(r.result.dists) for r in rows]),
        )

    ids_plain, d_plain = arrays(results["plain"])
    wall_plain, wall_off, wall_on = (
        min(walls[n]) for n in ("plain", "off", "on")
    )

    for name in ("off", "on"):
        ids, dists = arrays(results[name])
        assert np.array_equal(ids_plain, ids), (
            f"tracer-{name}: result ids diverged from untraced run"
        )
        assert np.array_equal(
            d_plain.view(np.uint32), dists.view(np.uint32)
        ), f"tracer-{name}: distances not bit-identical to untraced run"

    ratios = sorted(
        on / min(plain, off)
        for plain, off, on in zip(walls["plain"], walls["off"], walls["on"])
    )
    overhead = ratios[len(ratios) // 2] - 1.0
    return dict(
        qps_plain=len(q) / wall_plain,
        qps_disabled=len(q) / wall_off,
        qps_traced=len(q) / wall_on,
        overhead_traced=overhead,
        max_overhead=max_overhead,
        spans=len(tr),
        bit_identical=True,
        ok=overhead < max_overhead,
    )


def _serve_baseline(path: str) -> dict:
    """batch_size -> recorded serving point from BENCH_serve.json."""
    with open(path) as f:
        doc = json.load(f)
    return {pt["batch_size"]: pt for pt in doc["points"]}


def check_regression(points: list[dict], serve_path: str) -> list[str]:
    """Mean I/Os must match the recorded workload exactly; recall must not
    drop. Returns a list of failure strings (empty == pass)."""
    base = _serve_baseline(serve_path)
    failures = []
    for pt in points:
        ref = base.get(pt["batch_size"])
        if ref is None:
            continue
        if abs(pt["mean_ios"] - ref["mean_ios"]) > 1e-9:
            failures.append(
                f"batch{pt['batch_size']}: mean_ios {pt['mean_ios']} != "
                f"recorded {ref['mean_ios']}"
            )
        if pt["recall"] < ref["recall"] - 1e-9:
            failures.append(
                f"batch{pt['batch_size']}: recall {pt['recall']} < "
                f"recorded {ref['recall']}"
            )
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write BENCH_search.json here")
    ap.add_argument(
        "--check", default=None,
        help="BENCH_serve.json to gate mean_ios/recall against",
    )
    args = ap.parse_args(argv)
    points = sweep()
    serve = _serve_baseline(args.check) if args.check else {}
    for pt in points:
        ref = serve.get(pt["batch_size"])
        if ref:
            pt["serve_baseline_qps"] = ref["qps"]
            pt["speedup_vs_serve"] = pt["qps"] / ref["qps"]
        extra = (
            f"  speedup={pt['speedup_vs_serve']:.2f}x" if ref else ""
        )
        print(
            f"batch={pt['batch_size']:3d}  qps={pt['qps']:8.1f}  "
            f"per_hop={pt['per_hop_ms']:6.3f}ms  ios={pt['mean_ios']:6.2f}  "
            f"recall={pt['recall']:.4f}{extra}"
        )
    tracing = None
    if args.check:
        tracing = tracing_gate()
        print(
            f"tracing gate: bit_identical=True  "
            f"overhead={tracing['overhead_traced']:+.2%}  "
            f"(limit {tracing['max_overhead']:.0%}, "
            f"{tracing['spans']} spans recorded)"
        )
    if args.out:
        doc = dict(
            bench="search_hotpath",
            n=common.N,
            dim=common.D,
            queries=common.Q,
            k=K,
            platform=platform.platform(),
            points=points,
        )
        if tracing is not None:
            doc["tracing"] = tracing
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")
    if args.check:
        failures = check_regression(points, args.check)
        if not tracing["ok"]:
            failures.append(
                f"tracing overhead {tracing['overhead_traced']:+.2%} "
                f">= {tracing['max_overhead']:.0%} limit"
            )
        if failures:
            for f_ in failures:
                print(f"REGRESSION: {f_}")
            raise SystemExit(1)
        print(f"regression gate vs {args.check}: ok")


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
