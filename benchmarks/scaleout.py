"""Scale-out load generator: QPS-vs-shards against the HTTP frontend.

The scale-out claim is end to end: a data-sharded collection
(``repro.dist.ShardedPageStore``) served through the network frontend
(``repro.serve.http.HttpFrontend``) answers MORE queries per second than
the unsharded index at recall parity, and the admission-control surface
(deadline sheds, in-flight 503s) actually sheds. The server runs in this
process on its own thread (only one process may hold the chip), and
every request travels real HTTP + JSON.

Per shard count S in (1, 2, 4) it:

  * builds (or reloads from the bench cache) a one-collection database —
    unsharded ``PageANNIndex`` at S=1, ``ShardedPageStore`` otherwise,
  * loads it into a ``VectorService`` behind an ``HttpFrontend`` bound
    to an ephemeral port,
  * hammers ``POST /search`` with the full query batch for R rounds,
    recording QPS, wall-clock percentiles and recall@10,
  * on the 2-shard server, exercises load shedding: a batch with a
    sub-millisecond ``deadline_ms`` must come back 504 with the engine's
    ``sheds`` counter advanced, and a concurrent stampede against
    an in-flight cap of 2 must surface 503s in
    ``pageann_http_rejected_total{reason="inflight"}`` — both asserted
    from a real ``/metrics`` scrape.

Hard gates (CI): 2- and 4-shard recall within 0.02 of unsharded, QPS
scaling >= 1.6x at 2 shards, shed counters advanced, exposition parses.
Results land in ``BENCH_scaleout.json``.

  PYTHONPATH=src python -m benchmarks.scaleout [--smoke]
      [--out BENCH_scaleout.json]

``--smoke`` only trims the number of timed rounds — the gates and the
dataset are the full ones (the QPS ratio needs the real corpus).
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import platform
import time
import urllib.error
import urllib.request

import numpy as np

from benchmarks.common import (
    CACHE,
    base_cfg,
    cfg_digest,
    data_digest,
    dataset,
    pageann_index,
)
from repro.core import persist, recall_at_k
from repro.obs import parse_prometheus_text, sample_value

K = 10
SHARD_COUNTS = (1, 2, 4)
SCALING_FLOOR_2SHARD = 1.6
RECALL_PARITY_SLACK = 0.02


# --------------------------------------------------------------- databases
def _db_dir(tag: str, cfg, x) -> str:
    os.makedirs(CACHE, exist_ok=True)
    return os.path.join(
        CACHE, f"scaleout_{tag}_{cfg_digest(cfg)}_{data_digest(x)}"
    )


def build_databases(x, cfg) -> dict[int, str]:
    """One single-collection database directory per shard count, cached
    on disk across runs (keyed by config + data)."""
    from repro.dist import ShardedPageStore

    dirs = {}
    for s in SHARD_COUNTS:
        d = _db_dir(f"s{s}", cfg, x)
        if not persist.is_database_dir(d):
            if s == 1:
                index = pageann_index(x, cfg, "scaleout")
            else:
                index = ShardedPageStore.build(x, cfg, num_shards=s)
            persist.save_database({"wiki": index}, d)
        dirs[s] = d
    return dirs


# ------------------------------------------------------------------ server
class Frontend:
    """One database served in THIS process: ``VectorService.load`` +
    ``HttpFrontend`` on its own thread. The load generator shares the
    process, so one process holds the device."""

    def __init__(self, db_dir: str, *, batch: int, max_inflight: int):
        from repro.serve import HttpFrontend, VectorService

        self.svc = VectorService.load(db_dir, batch_size=batch)
        try:
            self.fe = HttpFrontend(
                self.svc, port=0, max_inflight=max_inflight
            )
        except Exception:
            self.svc.close()
            raise
        self.url = self.fe.url

    def close(self) -> None:
        self.fe.close()
        self.svc.close()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


def post(url: str, doc: dict, timeout: float = 300.0):
    req = urllib.request.Request(
        url, json.dumps(doc).encode(), {"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def get(url: str, timeout: float = 60.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


# -------------------------------------------------------------- load phases
def timed_rounds(url: str, q: np.ndarray, truth, rounds: int) -> dict:
    """R sequential full-batch search requests; returns QPS + percentiles
    + recall of the last response."""
    payload = {"collection": "wiki", "queries": q.tolist(), "k": K}
    code, doc = post(url + "/search", payload)   # warm (excluded)
    if code != 200:
        raise RuntimeError(f"warm search failed: {code} {doc}")
    walls = []
    t0 = time.perf_counter()
    for _ in range(rounds):
        t1 = time.perf_counter()
        code, doc = post(url + "/search", payload)
        walls.append((time.perf_counter() - t1) * 1e3)
        if code != 200:
            raise RuntimeError(f"timed search failed: {code} {doc}")
    wall_s = time.perf_counter() - t0
    ids = np.array([r["ids"] for r in doc["results"]])
    walls = np.asarray(walls)
    return dict(
        qps=rounds * len(q) / wall_s,
        recall=recall_at_k(ids, truth),
        wall_ms_mean=float(walls.mean()),
        wall_ms_p50=float(np.percentile(walls, 50)),
        wall_ms_p99=float(np.percentile(walls, 99)),
        requests=rounds,
        queries_per_request=len(q),
    )


def exercise_shedding(url: str, q: np.ndarray) -> dict:
    """Deadline sheds (504 + engine ``sheds``) and in-flight 503s, both
    confirmed from the /metrics exposition."""
    # 1) queue-deadline expiry: a microsecond deadline cannot survive the
    #    submit->flush gap, so every row sheds and the request is 504
    code, doc = post(url + "/search", {
        "collection": "wiki", "queries": q.tolist(), "k": K,
        "deadline_ms": 0.001,
    })
    deadline_code = code
    # 2) in-flight cap: a stampede of concurrent batches against
    #    an in-flight cap of 2 must shed some requests with 503
    payload = {"collection": "wiki", "queries": q[:8].tolist(), "k": K}
    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
        codes = list(pool.map(
            lambda _: post(url + "/search", payload)[0], range(16)
        ))
    parsed = parse_prometheus_text(get(url + "/metrics").decode())
    sheds = sample_value(parsed, "pageann_sheds_total")
    try:
        rejected_inflight = sample_value(
            parsed, "pageann_http_rejected_total", reason="inflight"
        )
    except KeyError:
        rejected_inflight = 0.0
    try:
        rejected_deadline = sample_value(
            parsed, "pageann_http_rejected_total", reason="deadline"
        )
    except KeyError:
        rejected_deadline = 0.0
    return dict(
        deadline_code=deadline_code,
        stampede_codes=sorted(set(codes)),
        http_503=sum(c == 503 for c in codes),
        sheds_total=sheds,
        rejected_inflight=rejected_inflight,
        rejected_deadline=rejected_deadline,
        metrics_series=len(parsed),
    )


# -------------------------------------------------------------------- main
def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_scaleout.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: fewer timed rounds, same gates")
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args(argv)
    rounds = args.rounds or (4 if args.smoke else 8)

    x, q, truth = dataset()
    cfg = base_cfg()
    dirs = build_databases(x, cfg)

    points = []
    shed = None
    for s in SHARD_COUNTS:
        with Frontend(dirs[s], batch=len(q), max_inflight=2) as fe:
            point = dict(shards=s, db_dir=dirs[s], **timed_rounds(
                fe.url, q, truth, rounds
            ))
            stats = json.loads(get(fe.url + "/stats"))
            m = stats.get("metrics", {})
            point["server"] = {
                key: m.get(key) for key in (
                    "requests", "batches", "sheds", "compile_misses",
                    "mean_batch_occupancy",
                )
            }
            if s == 2:
                shed = exercise_shedding(fe.url, q)
            points.append(point)
            print(
                f"shards={s}: qps={point['qps']:.0f} "
                f"recall={point['recall']:.3f} "
                f"p50={point['wall_ms_p50']:.1f}ms "
                f"p99={point['wall_ms_p99']:.1f}ms"
            )

    base = next(p for p in points if p["shards"] == 1)
    scaling = {
        str(p["shards"]): p["qps"] / base["qps"]
        for p in points if p["shards"] != 1
    }
    doc = dict(
        bench="scaleout",
        host=dict(
            platform=platform.platform(),
            python=platform.python_version(),
        ),
        collection="wiki",
        k=K,
        rounds=rounds,
        points=points,
        scaling_vs_unsharded=scaling,
        shed=shed,
    )

    # ------------------------------------------------------------- gates
    failures = []
    for p in points:
        if p["shards"] == 1:
            continue
        if p["recall"] < base["recall"] - RECALL_PARITY_SLACK:
            failures.append(
                f"recall parity: {p['shards']}-shard {p['recall']:.3f} < "
                f"unsharded {base['recall']:.3f} - {RECALL_PARITY_SLACK}"
            )
    if scaling.get("2", 0.0) < SCALING_FLOOR_2SHARD:
        failures.append(
            f"qps scaling at 2 shards {scaling.get('2', 0.0):.2f}x < "
            f"{SCALING_FLOOR_2SHARD}x"
        )
    if shed is None:
        failures.append("shed exercise never ran")
    else:
        if shed["deadline_code"] != 504:
            failures.append(
                f"deadline batch answered {shed['deadline_code']}, want 504"
            )
        if shed["sheds_total"] < len(q):
            failures.append(
                f"engine sheds_total {shed['sheds_total']} < {len(q)} "
                "(deadline batch not counted)"
            )
        if shed["http_503"] < 1 or shed["rejected_inflight"] < 1:
            failures.append(
                "in-flight stampede produced no 503 sheds "
                f"(503s={shed['http_503']}, "
                f"rejected={shed['rejected_inflight']})"
            )
        if shed["metrics_series"] < 10:
            failures.append(
                f"/metrics exposition suspiciously small "
                f"({shed['metrics_series']} series)"
            )
    doc["gates"] = dict(
        scaling_floor_2shard=SCALING_FLOOR_2SHARD,
        recall_parity_slack=RECALL_PARITY_SLACK,
        failures=failures,
    )
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {args.out}; scaling: " + ", ".join(
        f"{s} shards {v:.2f}x" for s, v in sorted(scaling.items())
    ))
    if failures:
        raise SystemExit("scaleout gates FAILED:\n  " + "\n  ".join(failures))
    print("scaleout gates ok")


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
