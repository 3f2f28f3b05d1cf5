"""Bring-up smoke of the page-node vector database on a TPU.

Drives the main path once, through the entry points a user calls, on
clustered vectors made from ``--seed``:

  a. device    a TPU must be present; the kernels dispatch to Pallas
  b. build     ``PageANNIndex.build`` (HYBRID) with a 10-value tag column,
               saved with ``persist.save_database`` into smoke_artifacts/
  c. resident  ``VectorService.load`` + ``HttpFrontend``; POST /search;
               recall@10 against exact brute force; the dispatched search
               executable holds the Pallas page scan (``tpu_custom_call``)
  d. streamed  the same database under a 0.25 memory budget: the same ids,
               with pages fetched from the host memmap
  e. filtered  a ~10%-selective tag predicate: every id passes it, recall
               against post-filter brute force
  f. writes    a ``MutableIndex`` collection over HTTP: /insert, each
               vector its own top-1, /delete, none comes back

With ``--chips 4`` it runs one phase instead: a 4-shard
``ShardedPageStore`` searched over a (4, 1) mesh, one shard per chip,
against the same store searched on one device; the ids must agree.

Every phase prints one line; any failed check exits non-zero. The last
line is ``{"ok": true, "device": {...}}``. Without a TPU it exits 1 and
prints no result. ``--cpu-rehearsal`` runs the phases on the CPU (with
the kernels' jnp oracles), skips the chip-only checks and also ends
without a result: it gives the CPU recall the chip is held to.

  python chip_smoke.py [--seed 0] [--n N] [--dim 128] [--chips 1|4]
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARTIFACTS = ROOT / "smoke_artifacts"   # rebuilt from scratch every run
K = 10
QUERIES, BATCH = 256, 64
INSERTS = 64
TAGS = 10                              # one tag value selects ~10%
DEFAULT_N = {1: 40000, 4: 8000}
# beam 64 (benchmarks/common.py) reaches recall@10 0.68 at N = 40000;
# 256 reaches 0.99. A filtered search widens its beam by the
# predicate's oversampling (16x at 10%), so it starts from 64.
BEAM, FILTERED_BEAM = 256, 64

# recall@10 of ``--cpu-rehearsal`` (XLA:CPU, x86) at the default seed and
# dim and each chip count's default N; the chip must come within
# RECALL_SLACK of it.
CPU_RECALL = {
    (0, 40000, 128): {"resident": 0.9875, "filtered": 0.982421875},
    (0, 8000, 128): {"sharded": 1.0},
}
RECALL_SLACK = 0.01
FALLBACK_FLOOR = 0.9                   # any other (seed, n, dim)


def _require(ok, message: str) -> None:
    """A failed check ends the run non-zero (asserts vanish under -O)."""
    if not ok:
        raise SystemExit(message)


def _post(url: str, doc: dict) -> dict:
    req = urllib.request.Request(
        url, json.dumps(doc).encode(), {"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise SystemExit(f"POST {url} -> {e.code}: {e.read()[:500]!r}")


def _search_http(url: str, name: str, q) -> tuple:
    """POST /search in BATCH-query requests -> ((Q, K) ids, seconds each)."""
    import numpy as np

    ids, secs = [], []
    for lo in range(0, len(q), BATCH):
        t0 = time.perf_counter()
        doc = _post(url + "/search", {
            "collection": name, "queries": q[lo:lo + BATCH].tolist(), "k": K,
        })
        secs.append(time.perf_counter() - t0)
        ids.extend(r["ids"] for r in doc["results"])
    return np.asarray(ids, np.int64), secs


def _floor(args, phase: str) -> float:
    cpu = CPU_RECALL.get((args.seed, args.n, args.dim), {}).get(phase)
    return FALLBACK_FLOOR if cpu is None else cpu - RECALL_SLACK


def _check_recall(args, phase: str, got: float) -> str:
    floor = _floor(args, phase)
    _require(got >= floor or args.cpu_rehearsal,
             f"{phase}: recall@{K} {got} below floor {floor}")
    return f"recall@{K}={got} (floor {floor})"


def _config(dim: int):
    from repro.core import MemoryMode, PageANNConfig

    # benchmarks/common.py's knobs but the beam; 16 PQ subspaces from
    # d = 128 up
    return PageANNConfig(
        dim=dim, graph_degree=24, build_beam=48,
        pq_subspaces=8 if dim <= 32 else 16,
        lsh_sample=1024, lsh_entries=12, beam_width=BEAM, max_hops=64,
        memory_mode=MemoryMode.HYBRID,
    )


def phase_device(args) -> dict:
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"a device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__} "
          f"kernels={ops._backend()}", flush=True)
    if not args.cpu_rehearsal:
        if dev["platform"] != "tpu":
            raise SystemExit(f"no TPU: JAX found {dev['platform']}")
        _require(ops._backend() == "tpu",
                 f"kernels dispatch to {ops._backend()}")
    if dev["count"] < args.chips:
        raise SystemExit(f"--chips {args.chips} but {dev['count']} devices")
    return dev


def phase_build(args, x, cats):
    from repro.core import MetadataSchema, PageANNIndex, persist

    t0 = time.perf_counter()
    index = PageANNIndex.build(
        x, _config(args.dim), schema=MetadataSchema(tags=("cat",)),
        metadata={"cat": [f"c{c}" for c in cats]},
    )
    build_s = time.perf_counter() - t0
    shutil.rmtree(ARTIFACTS, ignore_errors=True)
    db = ARTIFACTS / "db"
    persist.save_database({"wiki": index}, str(db))
    recs = index.data.page_recs
    print(f"b build: n={len(x)} dim={args.dim} build_s={build_s} "
          f"pages={recs.shape[0]} page_recs_device_bytes={recs.nbytes} "
          f"capacity={index.store.capacity}", flush=True)
    return db


def phase_resident(args, svc, url, q, truth):
    import jax.numpy as jnp

    from repro.core import recall_at_k
    from repro.core import search as search_mod

    _search_http(url, "wiki", q[:BATCH])               # compile + warm
    ids, secs = _search_http(url, "wiki", q)
    line = _check_recall(args, "resident", recall_at_k(ids, truth))
    idx = svc.index_of("wiki")
    hlo = search_mod.batch_search.lower(
        jnp.zeros((BATCH, args.dim), jnp.float32), idx.data,
        idx.resolve_params(K, None), capacity=idx.store.capacity,
        mode=idx.cfg.memory_mode.value,
    ).compile().as_text()
    pallas = "tpu_custom_call" in hlo
    if not args.cpu_rehearsal:
        _require(pallas, "the dispatched search holds no Pallas kernel")
    print(f"c resident: {line} pallas_page_scan={pallas} "
          f"warm_s_per_batch={secs}", flush=True)
    return ids


def phase_streamed(args, db, q, resident_ids):
    import numpy as np

    from repro.core import MemoryBudget
    from repro.serve import HttpFrontend, VectorService

    with VectorService.load(
        str(db), batch_size=BATCH,
        memory_budget=MemoryBudget(fraction=0.25),
    ) as svc, HttpFrontend(svc, port=0) as fe:
        ids, secs = _search_http(fe.url, "wiki", q)
        fetched = svc.metrics().pages_fetched
        st = svc.index_of("wiki").stats
    same = bool(np.array_equal(ids, resident_ids))
    print(f"d streamed: resident_pages={st.resident_pages}/{st.pages} "
          f"pages_fetched={fetched} ids_equal_resident={same} "
          f"warm_s_per_batch={secs[1:]}", flush=True)
    _require(same, "streamed ids differ from resident ids")
    _require(fetched > 0, "the streamed search fetched no page")


def phase_filtered(args, svc, x, q, cats):
    import numpy as np

    from repro.core import Tag, recall_at_k
    from repro.core.vamana import brute_force_knn

    params = svc.index_of("wiki").default_params.replace(
        beam_width=FILTERED_BEAM
    )
    rows = svc.search("wiki", q, k=K, params=params,
                      filter=Tag("cat") == "c0")
    ids = np.stack([np.asarray(r.result.ids).reshape(-1) for r in rows])
    passing = np.flatnonzero(cats == 0)
    truth = passing[brute_force_knn(x[passing], q, K)]
    _require((ids >= 0).all(), "filtered search returned fewer than k ids")
    _require((cats[ids] == 0).all(), "a returned id fails the predicate")
    line = _check_recall(args, "filtered", recall_at_k(ids, truth))
    print(f"e filtered: selectivity={len(passing) / len(x)} {line}",
          flush=True)


def phase_writes(args, svc, url, x):
    import numpy as np

    from repro.core import MutableIndex
    from repro.data.pipeline import query_vectors

    svc.create_collection(
        "live", MutableIndex(svc.index_of("wiki"), auto_compact=False)
    )
    new = query_vectors(x, INSERTS, seed=args.seed + 3, noise=0.5)
    ids = np.asarray(_post(url + "/insert", {
        "collection": "live", "vectors": new.tolist(),
        "metadata": {"cat": ["c0"] * INSERTS},
    })["ids"])
    found, _ = _search_http(url, "live", new)
    self_top1 = int((found[:, 0] == ids).sum())
    _require(self_top1 == INSERTS,
             f"{self_top1}/{INSERTS} inserts are their own top-1")
    removed = _post(url + "/delete", {
        "collection": "live", "ids": ids.tolist(),
    })["removed"]
    _require(removed == INSERTS, f"/delete removed {removed}/{INSERTS}")
    after, _ = _search_http(url, "live", new)
    back = int(np.isin(after, ids).sum())
    _require(back == 0, f"{back} deleted ids came back")
    print(f"f writes: inserted={INSERTS} self_top1={self_top1} "
          f"deleted={removed} returned_after_delete={back}", flush=True)


def phase_sharded(args, x, q, truth):
    import numpy as np

    from repro.core import compat, recall_at_k
    from repro.dist import ShardedPageStore

    t0 = time.perf_counter()
    store = ShardedPageStore.build(x, _config(args.dim), num_shards=4)
    build_s = time.perf_counter() - t0
    mesh = compat.make_mesh((4, 1), ("data", "model"))
    one = store.search(q, k=K)                       # host fan-out, device 0
    meshed = store.search(q, k=K, mesh=mesh)
    recs = store.mesh_data(mesh).data.page_recs
    shard_devs = {
        s.index[0].start or 0: s.device for s in recs.addressable_shards
    }
    distinct = len(set(shard_devs.values()))
    same = bool(np.array_equal(meshed.ids, one.ids))
    line = _check_recall(args, "sharded", recall_at_k(meshed.ids, truth))
    print(f"sharded: n={len(x)} shards=4 build_s={build_s} {line} "
          f"ids_equal_one_device={same} shard_devices="
          f"{[str(shard_devs[i]) for i in sorted(shard_devs)]}", flush=True)
    _require(same, "mesh ids differ from the one-device search")
    _require(distinct == 4, f"4 shards on {distinct} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=None,
                    help="corpus size (default 40000, or 8000 with "
                         "--chips 4)")
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU, skip chip-only checks, print "
                         "no result and exit 1")
    args = ap.parse_args(argv)
    if args.n is None:
        args.n = DEFAULT_N[args.chips]

    from repro.launch.jax_cache import use_persistent_cache

    cache_dir = use_persistent_cache()
    import jax.monitoring
    import numpy as np

    events = {"cache_hits": 0, "cache_misses": 0}

    def count(name: str, **_):
        key = name.rsplit("/", 1)[-1]
        if key in events:
            events[key] += 1

    jax.monitoring.register_event_listener(count)
    dev = phase_device(args)

    from repro.core.vamana import brute_force_knn
    from repro.data.pipeline import clustered_vectors, query_vectors

    x = clustered_vectors(args.n, args.dim, num_clusters=64, seed=args.seed)
    q = query_vectors(x, QUERIES, seed=args.seed + 1)
    truth = brute_force_knn(x, q, K)
    if args.chips == 4:
        phase_sharded(args, x, q, truth)
    else:
        from repro.serve import HttpFrontend, VectorService

        cats = np.random.default_rng(args.seed + 2).integers(0, TAGS, args.n)
        db = phase_build(args, x, cats)
        with VectorService.load(str(db), batch_size=BATCH) as svc, \
                HttpFrontend(svc, port=0) as fe:
            resident_ids = phase_resident(args, svc, fe.url, q, truth)
            phase_streamed(args, db, q, resident_ids)
            phase_filtered(args, svc, x, q, cats)
            phase_writes(args, svc, fe.url, x)
    print(f"compile cache: dir={cache_dir} hits={events['cache_hits']} "
          f"misses={events['cache_misses']}", flush=True)
    if args.cpu_rehearsal:
        print("cpu rehearsal: no chip result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
