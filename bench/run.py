"""One run of one cell of the benchmark, on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The data set is fixed and ``--seed`` draws the order of the requests
(corpus.py). What depends on the deployment's shape comes from the
configuration's kind, bench/kinds/<kind>.py (bench/spec.py says what it
gives); every step and check is this file's, loadgen.py's and check.py's:

  1. device  JAX must find a TPU and the chips the cell asks for; else the
             run exits 3 and prints no result.
  2. data    the kind's corpus and query pool, from the configuration and
             the mix.
  3. index   the index the kind builds from the corpus with the program
             (``PageANNIndex.build`` for ``vectors_l2``), saved in
             bench/dbcache/<digest>/, keyed by the kind's digest inputs,
             the kind's and the program's code and the device kind: the
             first run in a checkout builds it, later runs load it as a
             restarted server loads its saved index.
  4. serve   the kind attaches the saved index to ``VectorService``, under
             the configuration's memory budget, behind ``HttpFrontend``:
             the served path.
  5. warm    requests the kind makes of the first pool queries of the
             seed's own order, until one compiles nothing.
  6. window  the load generator (loadgen.py, a child process that never
             imports JAX) sends the mix; the window is ``--seconds`` long
             and starts after the mix's ramp. With ``--trace 1`` the
             profiler records a few seconds in its middle; the engine's
             counters are read at the window's edges all the same.
  7. judge   once the window has closed, the peak memory is read and the
             server closed, every answer of a request sent in the window
             is compared with the kind's exact reference (check.py).

The last line of standard output is the result, one JSON object; the
last lines of standard error are the numbers compared, each beside its
limit. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics (bench/metrics/<name>.py each).

``--cpu-rehearsal`` runs the same steps on the CPU at a tiny size (the
kernels' jnp oracles in place of Pallas), prints the numbers compared,
and exits 1 with no result: a CPU run has no device numbers.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import hashlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import check, corpus, spec, trace_reduce  # noqa: E402

COLLECTION = "corpus"
JAX_CACHE = ROOT / "bench" / "jaxcache"
DB_CACHE = ROOT / "bench" / "dbcache"
TRACE_DIR = ROOT / "bench" / "traces"
PEAKS = ROOT / "bench" / "peaks.json"
TRACE_S = 4.0           # traced seconds, centred in the window
SPAN = "bench.traced_span"  # host annotation of those seconds in the trace
START_S = 1.0           # for the generator to start before its first send
CLIENT_TIMEOUT_S = 120.0
WARM_TRIES = 4
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# what --cpu-rehearsal shrinks; every other setting is the cell's own
REHEARSAL = {"num_vectors": 1000, "pool": 128, "ramp_s": 0.5}
EXIT_NO_CHIP = 3


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Compiles:
    """Counts the executables JAX builds (each ``backend_compile`` event,
    whether compiled or read from the persistent cache)."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration_secs: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1


def device_info(chips: int, rehearsal: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if not rehearsal:
        if dev["platform"] != "tpu":
            raise NoChip(f"no TPU: JAX found {dev['platform']}")
        if dev["count"] < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{dev['count']}")
    return dev


def db_digest(kind, cfg: dict, device_kind: str) -> str:
    """Key of a built index: what it was built from and by."""
    h = hashlib.sha256(json.dumps({
        "data": kind.digest_inputs(cfg), "device_kind": device_kind,
    }, sort_keys=True).encode())
    code = sorted((ROOT / "src" / "repro").rglob("*.py"))
    for p in code + [ROOT / "bench" / "corpus.py"]:
        h.update(str(p.relative_to(ROOT)).encode())
        h.update(p.read_bytes())
    h.update(Path(kind.__file__).read_bytes())
    return h.hexdigest()[:24]


def build_index(kind, cfg: dict, data, path: Path) -> float:
    """Build the kind's index of ``data`` and save it at ``path``; returns
    seconds."""
    t = time.monotonic()
    tmp = path.with_name(path.name + ".tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    kind.build(cfg, data, str(tmp))
    os.replace(tmp, path)
    return time.monotonic() - t


def snapshot(svc) -> dict:
    return svc.metrics()._asdict()


def sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


def warm_up(url: str, kind, data, mix: dict, seed: int,
            compiles: Compiles, k: int) -> int:
    """Send the requests the kind makes of the first pool queries of the
    seed's own order, a request of the mix's size each, until one compiles
    nothing; returns how many were sent."""
    from bench import loadgen

    s = {"collection": COLLECTION, "k": k, "timeout_s": CLIENT_TIMEOUT_S,
         "conn": loadgen.conn_args(url)}
    qpr = mix["queries_per_request"]
    n_pool, body = loadgen.bodies(kind, s, data.pool)
    order = corpus.query_order(seed, 0, n_pool, WARM_TRIES * qpr)
    for tries in range(1, WARM_TRIES + 1):
        before = compiles.count
        rows: list = []
        loadgen.request(s, body, order[(tries - 1) * qpr:tries * qpr],
                        time.monotonic(), rows)
        if rows[0][3] != 200:
            raise RuntimeError(f"warm-up request -> HTTP {rows[0][3]}")
        if compiles.count == before:
            return tries
    raise RuntimeError(f"still compiling after {WARM_TRIES} warm-up requests")


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    counters: dict            # {"start", "end"}: engine snapshots
    counter_s: float          # seconds between the two snapshots
    compiles: int             # executables built inside the window
    trace: object = None      # trace_reduce.Trace of the traced seconds
    trace_counters: dict | None = None
    loadgen: dict | None = None


def drive(url: str, kind, mix: dict, pool: dict, seed: int, seconds: float,
          svc, compiles: Compiles, trace: bool, k: int) -> Window:
    """Run the load generator through one window; snapshot the engine's
    counters at its edges and trace its middle."""
    t_start = time.monotonic() + START_S
    t0 = t_start + mix["ramp_s"]
    t_end = t0 + seconds
    settings = dict(
        mix, url=url, collection=COLLECTION, k=k, seed=seed,
        t_start=t_start, t0=t0, t_end=t_end, timeout_s=CLIENT_TIMEOUT_S,
        kind_file=kind.__file__,
    )
    buf = io.BytesIO()
    np.savez(buf, **pool)
    payload = json.dumps(settings).encode() + b"\n" + buf.getvalue()
    child = subprocess.Popen(
        [sys.executable, str(ROOT / "bench" / "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    got: dict = {}

    def talk():
        got["out"], got["err"] = child.communicate(payload)

    talker = threading.Thread(target=talk, daemon=True)
    talker.start()
    # the counters are read at the window's end on a timer: with a trace,
    # stopping the profiler takes many seconds past that end
    edge: dict = {}
    at_end = threading.Timer(
        0, lambda: edge.update(c=compiles.count, m=snapshot(svc),
                               s=time.monotonic()))
    try:
        sleep_until(t0)
        c0, m0, s0 = compiles.count, snapshot(svc), time.monotonic()
        at_end.interval = t_end - s0
        at_end.start()
        traced_s = tcount = traced = None
        if trace:
            traced_s, tcount = _trace(svc, (t0 + t_end - TRACE_S) / 2)
        at_end.join()
        talker.join(t_end - t_start + CLIENT_TIMEOUT_S + 60)
        # read once the window has closed: the reading takes host time
        if trace:
            traced = trace_reduce.read(str(TRACE_DIR), traced_s, SPAN)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
    finally:
        at_end.cancel()
        if child.poll() is None:
            child.kill()
        child.wait()
    if child.returncode != 0 or "out" not in got:
        raise RuntimeError(
            f"load generator exited {child.returncode}: "
            f"{got.get('err', b'')[-2000:].decode(errors='replace')}"
        )
    lg = dict(np.load(io.BytesIO(got["out"]), allow_pickle=False))
    return Window(t0, t_end, {"start": m0, "end": edge["m"]}, edge["s"] - s0,
                  edge["c"] - c0, traced, tcount, lg)


def _trace(svc, at: float):
    """Profile TRACE_S seconds from ``at`` into TRACE_DIR: (the traced
    span's host seconds, engine snapshots at its edges)."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host events from C++ only: small
    sleep_until(at)
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with jax.profiler.TraceAnnotation(SPAN):
        a, ta = snapshot(svc), time.monotonic()
        time.sleep(TRACE_S)
        b, tb = snapshot(svc), time.monotonic()
    jax.profiler.stop_trace()
    return tb - ta, {"start": a, "end": b}


def client_view(w: Window, qpr: int, seconds: float) -> dict:
    """What the clients saw: rates, latencies and the answers to judge."""
    lg = w.loadgen
    ok = lg["ok"]
    whole = ok.all(axis=1) & (lg["status"] == 200)
    in_win = (lg["t_due"] >= w.t0) & (lg["t_due"] < w.t_end)
    done_in_win = (lg["t_recv"] >= w.t0) & (lg["t_recv"] <= w.t_end)
    lat = (lg["t_recv"] - lg["t_due"]) * 1e3
    # a request that failed counts as beyond every percentile: as long as
    # the longest a client waits
    lat = np.where(whole, lat, CLIENT_TIMEOUT_S * 1e3)[in_win]
    sel = ok & in_win[:, None]
    return {
        "attempted": int(in_win.sum()) * qpr,
        "failed": int(in_win.sum()) * qpr - int(sel.sum()),
        "qps": float(ok[done_in_win].sum()) / seconds,
        "latency_ms": lat,
        "late_ms": ((lg["t_send"] - lg["t_due"]) * 1e3)[in_win],
        "qidx": lg["qidx"][sel],
        "ids": lg["ids"][sel],
        "dists": lg["dists"][sel],
    }


def end_to_end(cell, view: dict, recall: float, setup_s: float) -> dict:
    values = {
        "qps": view["qps"],
        "latency_p50_ms": float(np.percentile(view["latency_ms"], 50)),
        "latency_p95_ms": float(np.percentile(view["latency_ms"], 95)),
        "recall_at_10": recall,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell, record: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"], cell.root)(record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             rehearsal: bool = False, db_cache: Path | None = None,
             t_process: float = T_PROCESS, log=print) -> dict:
    """Steps 1-7 of the module docstring; returns the result object
    (``checks`` last). ``log`` takes the lines meant for standard error."""
    import jax

    cfg, mix = dict(cell.config), dict(cell.traffic)
    if rehearsal:
        cfg["num_vectors"] = REHEARSAL["num_vectors"]
        mix["pool"] = REHEARSAL["pool"]
        mix["ramp_s"] = REHEARSAL["ramp_s"]
    k = mix["k"]
    dev = device_info(cell.chips, rehearsal)
    peaks = None
    if not rehearsal:
        table = json.loads(PEAKS.read_text())["devices"]
        if dev["kind"] not in table:
            raise KeyError(f"no peaks for device kind {dev['kind']!r}")
        peaks = table[dev["kind"]]

    kind = cell.kind
    data = kind.data(cfg, mix)
    db = (db_cache or DB_CACHE) / db_digest(kind, cfg, dev["kind"])
    # the build makes the data set into an index once per checkout: the
    # first run pays it, later runs load what it saved, so it is logged on
    # its own line and left out of setup_s
    build_s = 0.0
    if not (db / "manifest.json").exists():
        build_s = build_index(kind, cfg, data, db)
        log(f"build {build_s:.3f}s (build+save, "
            f"N = {len(data.corpus['vectors'])})")
    else:
        log("build 0s (index from the cache)")
    paid = []

    from repro.core import MemoryBudget
    from repro.serve import HttpFrontend, VectorService

    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        t = time.monotonic()
        frac = cfg["memory_budget_fraction"]
        svc = VectorService(batch_size=cfg["serving"]["batch_size"])
        try:
            kind.attach(svc, COLLECTION, str(db), cfg, k,
                        MemoryBudget(fraction=frac) if frac else None)
            with HttpFrontend(
                svc, port=0, max_inflight=cfg["serving"]["max_inflight"]
            ) as fe:
                paid.append(f"load {time.monotonic() - t:.3f}s")
                t = time.monotonic()
                n_warm = warm_up(fe.url, kind, data, mix, seed, compiles, k)
                paid.append(f"warm-up {time.monotonic() - t:.3f}s "
                            f"({n_warm} requests, {compiles.count} "
                            f"executables)")
                record_bytes = kind.record_bytes(svc.index_of(COLLECTION))
                w = drive(fe.url, kind, mix, data.pool, seed, seconds, svc,
                          compiles, trace and not rehearsal, k)
            peak = dev_memory_peak()
        finally:
            svc.close()
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    setup_s = w.t0 - t_process - build_s
    log(f"setup {setup_s:.3f}s (process start to window start, less the "
        f"build): " + "; ".join(paid))

    qpr = mix["queries_per_request"]
    view = client_view(w, qpr, w.t_end - w.t0)
    late = view["late_ms"]
    log(f"window {w.t_end - w.t0:.3f}s (counters over {w.counter_s:.3f}s): "
        f"{view['attempted']} queries attempted, {view['failed']} failed; "
        f"generator late by "
        f"{float(np.max(late)) if len(late) else 0.0:.3f} ms at most")
    resident = None
    if frac:
        t = time.monotonic()
        resident = resident_answers(kind, db, cfg, data, view["qidx"], k)
        log(f"resident witness {time.monotonic() - t:.3f}s")
    checks = check.compare(
        kind, data, view["qidx"], view["ids"], view["dists"],
        unanswered=view["failed"], recall_floor=cfg["recall_floor"],
        resident_ids=resident,
    )
    recall = 1.0 - checks[f"miss_at_{k}"]["value"]
    device = dict(dev, memory_peak_bytes=peak)
    result = {
        "correct": check.passed(checks),
        "attempted": view["attempted"],
        "failed": view["failed"],
    }
    if trace:
        record = {
            "seconds": w.counter_s, "counters": w.counters,
            "trace_counters": w.trace_counters, "trace": w.trace,
            "compiles_in_window": w.compiles, "record_bytes": record_bytes,
            "peaks": peaks,
        }
        result["metrics"] = per_layer(cell, record)
        if w.trace is not None:
            device["busy_s"] = trace_reduce.busy_s(w.trace)
            device["window_s"] = w.trace.window_s
            result["breakdown"] = trace_reduce.breakdown(w.trace)
    else:
        result["metrics"] = end_to_end(cell, view, recall, setup_s)
    result["device"] = device
    result["checks"] = checks
    return result


def resident_answers(kind, db: Path, cfg: dict, data, qidx: np.ndarray,
                     k: int) -> np.ndarray:
    """The witness of a memory budget's guarantee: the same saved index
    attached by the kind with every page in HBM, asked the pool queries
    ``qidx`` in full batches through the same engine. Returns (A, k) ids,
    row i the answer to ``qidx[i]``."""
    from repro.serve import VectorService

    asked = np.unique(qidx)
    with VectorService(batch_size=cfg["serving"]["batch_size"]) as svc:
        kind.attach(svc, COLLECTION, str(db), cfg, k, None)
        got = svc.search(COLLECTION, data.pool["queries"][asked], k=k)
    ids = np.stack([np.asarray(r.result.ids, np.int64).reshape(-1)
                    for r in got])
    return ids[np.searchsorted(asked, qidx)]


def dev_memory_peak() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at a tiny size; no result")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax

    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    else:
        # in the checkout, at a fixed path, so later runs find it
        jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          rehearsal=args.cpu_rehearsal,
                          log=lambda s: print(s, file=sys.stderr, flush=True))
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for line in check.lines(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    if args.cpu_rehearsal:
        print("cpu rehearsal: no result", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
