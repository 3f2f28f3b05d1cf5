"""Paper Fig. 12: throughput/latency vs concurrency.

The paper scales query *threads*; the TPU-native analog is the vmapped
query batch dimension. Near-linear QPS scaling with batch = the same
property (fixed per-query work, amortized dispatch).
"""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.core import recall_at_k
from repro.data.pipeline import query_vectors


def run() -> list[str]:
    x, q, truth = common.dataset()
    cfg = common.base_cfg()
    idx = common.pageann_index(x, cfg, "scale")
    rows = []
    base_qps = None
    for batch in (1, 4, 16, 64):
        qb = query_vectors(x, batch, seed=7)
        res, dt = common.timeit(lambda: idx.search(qb, k=10))
        qps = batch / dt
        if base_qps is None:
            base_qps = qps
        rows.append(
            f"scaling_batch{batch},{1e6 * dt / batch:.1f},"
            f"qps={qps:.0f};speedup_vs_b1={qps / base_qps:.2f}x"
        )
    return rows


def main():
    for r in run():
        print(r)


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
