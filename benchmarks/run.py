"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each module's docstring
for the paper artifact it reproduces). The roofline/dry-run tables live in
``roofline_report`` and read experiments/dryrun/*.json.
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (
        adaptive,
        build_overhead,
        filter_cache,
        memory_sweep,
        read_amplification,
        recall_io,
        scaling,
        serve_throughput,
    )

    # benchmarks.search_hotpath, benchmarks.churn, and
    # benchmarks.serve_database are NOT registered here: CI runs each as
    # its own gated step (--check BENCH_serve.json / --smoke) right after
    # this harness, and registering them too would pay for their sweeps
    # twice. benchmarks.adaptive IS registered: its CI step runs only the
    # tiny --smoke gate (fresh 1200-vector index), so the full sweep here
    # is not duplicated work.
    modules = [
        ("table1_read_amplification", read_amplification),
        ("fig7_8_table3_recall_io", recall_io),
        ("fig10_11_table4_memory_sweep", memory_sweep),
        ("fig12_scaling", scaling),
        ("table5_build_overhead", build_overhead),
        ("adaptive_engine", adaptive),
        ("serve_throughput", serve_throughput),
        ("filter_cache", filter_cache),
    ]
    failures = 0
    print("name,us_per_call,derived")
    for name, mod in modules:
        t0 = time.perf_counter()
        try:
            for row in mod.run():
                print(row)
            print(f"{name}__wall,{1e6 * (time.perf_counter() - t0):.0f},ok")
        except Exception as e:
            failures += 1
            print(f"{name}__wall,0,FAILED:{e!r}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
