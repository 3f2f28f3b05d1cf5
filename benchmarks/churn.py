"""Mixed read-write churn benchmark for the mutable index.

Drives a ``core.delta.MutableIndex`` through a 95/5 read-write workload
(the classic serving mix): reads are batched top-10 searches through the
unified fresh+disk path, writes are small insert batches plus base-id
deletes (tombstones). At several delta-fill levels it records

  * read throughput (QPS) and write throughput (vectors/s) of the mixed
    loop,
  * mean disk I/Os per query (the delta scan adds zero page reads — I/O
    stays flat as the delta fills; the scan cost shows up in QPS),
  * recall@10 against brute-force ground truth over the CURRENT live set
    (base ∪ inserts − deletes),

then triggers ``compact()`` and records the post-compaction operating
point (delta folded in, tombstones gone) plus the compaction wall time.
Results land in ``BENCH_churn.json``.

  PYTHONPATH=src python -m benchmarks.churn [--out BENCH_churn.json]
      [--smoke]

``--smoke`` is the CI gate: a tiny dataset, a few hundred inserts +
deletes and one compaction, with a hard recall assertion.
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

from repro.core import MutableIndex, PageANNIndex, recall_at_k
from repro.core.vamana import brute_force_knn
from repro.data.pipeline import clustered_vectors, query_vectors

K = 10
READ_BATCH = 16
READ_FRACTION = 0.95
# one write op inserts INSERT_CHUNK vectors; every DELETE_EVERY write ops
# also deletes one live base id (tombstone pressure rides along)
INSERT_CHUNK = 8
DELETE_EVERY = 4


class _Workload:
    """The dataset split into a built base and an insert stream, with a
    live mask tracking (base ∪ inserts − deletes). External ids are row
    indices into the full dataset, so ground truth is a brute-force scan
    over the live rows."""

    def __init__(self, x: np.ndarray, queries: np.ndarray, n_base: int,
                 seed: int = 7):
        self.x = x
        self.queries = queries
        self.live = np.zeros(len(x), bool)
        self.live[:n_base] = True
        self.cursor = n_base          # next stream row to insert
        self.rng = np.random.default_rng(seed)

    def insert_op(self, index: MutableIndex) -> int:
        take = min(INSERT_CHUNK, len(self.x) - self.cursor)
        if take == 0:
            return 0
        rows = np.arange(self.cursor, self.cursor + take)
        index.insert(self.x[rows], ids=rows)
        self.live[rows] = True
        self.cursor += take
        return take

    def delete_op(self, index: MutableIndex, n_base: int) -> int:
        live_base = np.nonzero(self.live[:n_base])[0]
        if live_base.size == 0:
            return 0
        victim = self.rng.choice(live_base, size=1)
        index.delete(victim)
        self.live[victim] = False
        return 1

    def recall(self, index: MutableIndex) -> float:
        live_rows = np.nonzero(self.live)[0]
        truth_local = brute_force_knn(self.x[live_rows], self.queries, K)
        truth = live_rows[truth_local]
        res = index.search(self.queries, k=K)
        return recall_at_k(np.asarray(res.ids), truth)


def _mixed_phase(
    index: MutableIndex, work: _Workload, n_base: int, target_fraction: float
) -> dict:
    """Run the 95/5 mix until the delta reaches ``target_fraction`` of the
    base; returns throughput/IO measured over the whole phase."""
    reads_per_write = round(READ_FRACTION / (1 - READ_FRACTION))
    queries = work.queries
    nq = queries.shape[0]
    t0 = time.perf_counter()
    q_done = 0
    v_written = 0
    ios = []
    writes = 0
    while index.delta_fraction < target_fraction and work.cursor < len(work.x):
        v_written += work.insert_op(index)
        writes += 1
        if writes % DELETE_EVERY == 0:
            v_written += work.delete_op(index, n_base)
        for r in range(reads_per_write):
            lo = (q_done % nq)
            batch = np.take(
                queries, range(lo, lo + READ_BATCH), axis=0, mode="wrap"
            )
            res = index.search(batch, k=K)
            ios.append(np.asarray(res.ios))
            q_done += READ_BATCH
    wall = time.perf_counter() - t0
    return dict(
        read_qps=q_done / wall if wall > 0 else 0.0,
        write_vps=v_written / wall if wall > 0 else 0.0,
        queries=q_done,
        writes=v_written,
        mean_ios=float(np.concatenate(ios).mean()) if ios else 0.0,
        wall_s=wall,
    )


def _point(index: MutableIndex, work: _Workload, phase: str, **extra) -> dict:
    s = index.stats
    return dict(
        phase=phase,
        delta_fraction=round(index.delta_fraction, 4),
        delta_live=s.delta_live,
        tombstones=s.tombstones,
        base_rows=s.base_rows,
        generation=s.generation,
        recall=work.recall(index),
        **extra,
    )


def run(
    n: int, n_base: int, dim: int, q: int, fill_levels, cfg
) -> dict:
    x = clustered_vectors(n, dim, num_clusters=max(8, n // 125), seed=0)
    queries = query_vectors(x, q, seed=1)

    t0 = time.perf_counter()
    base = PageANNIndex.build(x[:n_base], cfg)
    build_s = time.perf_counter() - t0

    index = MutableIndex(base, auto_compact=False)
    work = _Workload(x, queries, n_base)

    # static reference point: the read-only path before any write
    static = index.search(queries, k=K)
    points = [
        _point(
            index, work, "static",
            read_qps=0.0, write_vps=0.0,
            mean_ios=float(np.asarray(static.ios).mean()),
        )
    ]
    for level in fill_levels:
        mixed = _mixed_phase(index, work, n_base, level)
        points.append(_point(index, work, "churn", **mixed))
        pt = points[-1]
        print(
            f"fill={pt['delta_fraction']:.3f}  read_qps={pt['read_qps']:8.1f}  "
            f"write_vps={pt['write_vps']:7.1f}  ios={pt['mean_ios']:6.2f}  "
            f"recall={pt['recall']:.4f}  (tombstones={pt['tombstones']})"
        )

    t0 = time.perf_counter()
    compacted = index.compact()
    compact_s = time.perf_counter() - t0
    post = index.search(queries, k=K)
    points.append(
        _point(
            index, work, "post_compact",
            read_qps=0.0, write_vps=0.0,
            mean_ios=float(np.asarray(post.ios).mean()),
            compact_s=compact_s, compacted=compacted,
        )
    )
    pt = points[-1]
    print(
        f"post-compact: gen={pt['generation']} ios={pt['mean_ios']:6.2f} "
        f"recall={pt['recall']:.4f} (rebuild {compact_s:.1f}s)"
    )
    return dict(
        bench="churn",
        n=n, n_base=n_base, dim=dim, queries=q, k=K,
        read_fraction=READ_FRACTION,
        read_batch=READ_BATCH,
        base_build_s=build_s,
        platform=platform.platform(),
        points=points,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write BENCH_churn.json here")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny CI gate: few hundred inserts+deletes, one compaction, "
             "hard recall assertion",
    )
    args = ap.parse_args(argv)

    if args.smoke:
        from repro.core import MemoryMode, PageANNConfig

        cfg = PageANNConfig(
            dim=32, graph_degree=12, build_beam=24, pq_subspaces=8,
            lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
            memory_mode=MemoryMode.HYBRID,
        )
        doc = run(
            n=1200, n_base=900, dim=32, q=16,
            fill_levels=(0.1, 0.2, 0.32), cfg=cfg,
        )
    else:
        from benchmarks import common

        doc = run(
            n=common.N, n_base=int(common.N * 0.8), dim=common.D,
            q=common.Q, fill_levels=(0.05, 0.125, 0.25),
            cfg=common.base_cfg(),
        )

    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")

    if args.smoke:
        static_recall = doc["points"][0]["recall"]
        floor = static_recall - 0.02
        for pt in doc["points"]:
            if pt["recall"] < floor:
                raise SystemExit(
                    f"CHURN REGRESSION: {pt['phase']} recall {pt['recall']:.4f}"
                    f" < static {static_recall:.4f} - 0.02"
                )
        last = doc["points"][-1]
        assert last["phase"] == "post_compact" and last["generation"] >= 1
        assert last["tombstones"] == 0 and last["delta_live"] == 0
        print(
            f"churn smoke ok: recall stayed >= {floor:.4f} across "
            f"{len(doc['points'])} points incl. one compaction"
        )


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
