"""Whole runs of each cell on the CPU at a tiny size (``--cpu-rehearsal``):
sound, they are correct; with the served answers broken underneath, they
are not; and no run without a TPU prints a result."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, spec  # noqa: E402
from repro.core import index as index_mod  # noqa: E402
from repro.core import search as search_mod  # noqa: E402
from repro.core import stream as stream_mod  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 11


@pytest.fixture(scope="module")
def db_cache(tmp_path_factory):
    return tmp_path_factory.mktemp("dbcache")


def rehearse(cell: str, db_cache, trace: bool = False) -> dict:
    return run.run_cell(spec.load_cell(cell), SEED, 1.0, trace,
                        rehearsal=True, db_cache=db_cache,
                        t_process=time.monotonic(), log=lambda line: None)


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_rehearsal_is_correct(cell, db_cache):
    r = rehearse(cell, db_cache)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {m["name"] for m in spec.load_cell(cell).end_to_end}
    assert set(r["metrics"]) == want
    assert list(r)[-1] == "checks"


def test_a_traced_rehearsal_reads_the_counters(db_cache):
    r = rehearse("bigann128-budget25.bulk64", db_cache, trace=True)
    assert r["correct"], r["checks"]
    # the CPU has no device trace: only the counters' metrics are read
    assert set(r["metrics"]) == {
        "compiles_in_window", "pages_per_query",
        "fetch_wall_share", "pages_fetched_per_query"}
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert r["metrics"]["fetch_wall_share"]["value"] > 0


def test_an_open_loop_rehearsal_is_correct(db_cache):
    """The generator's open loop (one-query requests at a fixed rate, the
    same gaps for every seed in a seeded order) through the whole run, and
    the latency percentiles it reports."""
    bulk = spec.load_cell("bigann128-hbm.bulk64")
    cell = dataclasses.replace(
        bulk, name="bigann128-hbm.open",
        traffic={"loop": "open", "rate": 20.0, "max_outstanding": 64,
                 "queries_per_request": 1, "k": 10, "pool": 2048,
                 "query_noise": 0.1, "ramp_s": 0.5},
        end_to_end=tuple(
            {"name": n, "unit": "ms"}
            for n in ("latency_p50_ms", "latency_p95_ms")) + bulk.end_to_end)
    r = run.run_cell(cell, SEED, 2.0, False, rehearsal=True,
                     db_cache=db_cache, t_process=time.monotonic(),
                     log=lambda line: None)
    assert r["correct"], r["checks"]
    assert 20 <= r["attempted"] <= 80 and r["failed"] == 0
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 0 < m["latency_p50_ms"] <= m["latency_p95_ms"]


def test_the_counter_window_ends_with_the_window_while_tracing(
        db_cache, monkeypatch):
    """Stopping the profiler takes many seconds, past the window's end:
    the engine's counters are read at the window's end all the same."""
    orig = run.drive

    def slow_trace(svc, at):
        run.sleep_until(at)
        time.sleep(3.0)
        return 0.5, {"start": run.snapshot(svc), "end": run.snapshot(svc)}

    def traced_drive(*args):
        return orig(*args[:8], True, *args[9:])

    monkeypatch.setattr(run, "_trace", slow_trace)
    monkeypatch.setattr(run.trace_reduce, "read", lambda *a: None)
    monkeypatch.setattr(run, "drive", traced_drive)
    lines = []
    r = run.run_cell(spec.load_cell("bigann128-budget25.bulk64"), SEED, 1.0,
                     False, rehearsal=True, db_cache=db_cache,
                     t_process=time.monotonic(), log=lines.append)
    assert r["correct"], r["checks"]
    window = next(ln for ln in lines if ln.startswith("window"))
    counter_s = float(window.split("counters over ")[1].split("s)")[0])
    assert abs(counter_s - 1.0) < 0.5, window


def _broken(fn):
    """Wrap ``PageANNIndex.search`` so ``fn`` alters what it produces."""
    orig = index_mod.PageANNIndex.search

    def search(self, queries, k=None, params=None, **kw):
        res = orig(self, queries, k, params, **kw)
        ids, dists = fn(np.array(res.ids), np.array(res.dists),
                        self.store.num_vectors)
        return res._replace(ids=ids, dists=dists)

    return search


def altered_answer(ids, dists, n):
    ids[:, 0] = (ids[:, 0] + 1) % n
    return ids, dists


def wrong_request(ids, dists, n):
    """The demux hands each request its neighbour's answer."""
    return np.roll(ids, 1, axis=0), np.roll(dists, 1, axis=0)


def half_batch(ids, dists, n):
    """Half of each batch left out, given the other half's answers."""
    h = len(ids) // 2
    ids[h:], dists[h:] = ids[:len(ids) - h], dists[:len(ids) - h]
    return ids, dists


FAULTS = {"altered_answer": altered_answer, "wrong_request": wrong_request,
          "half_batch": half_batch, "state_unchanged": None}
CASES = [(c, f) for c in CELLS for f in FAULTS]


@pytest.mark.parametrize("cell,fault", CASES)
def test_a_broken_search_is_not_correct(cell, fault, db_cache, monkeypatch):
    if fault == "state_unchanged":
        # every hop returns the beam it was given: the search never moves
        # from its entry points
        monkeypatch.setattr(
            search_mod, "merge",
            lambda state, *a, **kw: state._replace(hops=state.hops + 1))
    else:
        monkeypatch.setattr(index_mod.PageANNIndex, "search",
                            _broken(FAULTS[fault]))
    jax.clear_caches()
    try:
        r = rehearse(cell, db_cache)
    finally:
        jax.clear_caches()
    assert not r["correct"], r["checks"]


def test_a_wrong_streamed_page_is_not_correct(db_cache, monkeypatch):
    """The host fetch hands back the next page's record in place of the
    one asked for: the answers still name distinct corpus ids with their
    exact distances, and the resident witness tells them apart."""
    orig = stream_mod.PageFetcher.__call__

    def shifted(self, ids):
        ids = np.asarray(ids)
        return orig(self, np.where(ids >= 0, (ids + 1) % self.num_pages, ids))

    monkeypatch.setattr(stream_mod.PageFetcher, "__call__", shifted)
    jax.clear_caches()
    try:
        r = rehearse("bigann128-budget25.bulk64", db_cache)
    finally:
        jax.clear_caches()
    assert not r["correct"], r["checks"]
    assert r["checks"]["streamed_vs_resident"]["value"] > 0


def test_the_cli_rehearsal_prints_no_result(db_cache, monkeypatch, capsys):
    monkeypatch.setattr(run, "DB_CACHE", db_cache)
    rc = run.main(["--workload", "bigann128-hbm.bulk64", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0", "--cpu-rehearsal"])
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "check dist_gap" in out.err


def _bench(cwd: Path, *extra) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bigann128-hbm.bulk64",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_without_a_tpu_there_is_no_result():
    p = _bench(ROOT)
    assert p.returncode == run.EXIT_NO_CHIP and p.stdout == ""
    assert "no TPU" in p.stderr


def test_the_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(
                            "dbcache", "jaxcache", "traces", "__pycache__"))
    p = _bench(tmp_path, "--cpu-rehearsal")
    assert p.returncode != 0 and p.stdout == ""
