"""The search and the serving path, as a profiler trace sees them: the hop
stages' named scopes in the lowered program, the host spans of the HTTP
frontend, the engine and the page fetcher in a ``jax.profiler`` trace, and
the cumulative hop and shared-page counters."""
import glob
import json
import re
import sys
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    MemoryBudget,
    MemoryMode,
    PageANNConfig,
    PageANNIndex,
    SearchParams,
    load_index,
)
from repro.core import search as search_mod
from repro.data.pipeline import clustered_vectors, query_vectors
from repro.obs import Tracer, phase
from repro.serve import BatchingEngine, HttpFrontend, VectorService

ROOT = Path(__file__).resolve().parents[1]
N, D = 600, 32
# the hop's stages, as core/search.py scopes them
STAGES = {"hop_select", "hop_scan", "hop_fetch", "hop_cache_probe",
          "hop_nbr_adc", "hop_cand_probe", "hop_dedupe", "hop_merge"}


def _cfg(mode=MemoryMode.HYBRID, **kw):
    return PageANNConfig(
        dim=D, graph_degree=12, build_beam=24, pq_subspaces=8,
        lsh_sample=256, lsh_entries=8, beam_width=32, max_hops=24,
        memory_mode=mode, **kw,
    )


@pytest.fixture(scope="module")
def corpus():
    x = clustered_vectors(N, D, num_clusters=8, seed=0)
    # repeated queries: lanes of one batch then read the same pages
    q = np.concatenate([query_vectors(x, 4, seed=1)] * 2)
    return x, q


@pytest.fixture(scope="module")
def artifact(corpus, tmp_path_factory):
    """A saved index, warmed so a budgeted load has a page order."""
    x, q = corpus
    idx = PageANNIndex.build(x, _cfg(cache_pages=8))
    idx.warm_cache(q)
    path = str(tmp_path_factory.mktemp("hops") / "idx.pageann")
    idx.save(path)
    return path


def _scopes(text: str) -> set[str]:
    return set(re.findall(r"hop_[a-z_]+", " ".join(
        re.findall(r'op_name="([^"]*)"', text))))


def _hlo(index, q, *, fetcher=None) -> str:
    """The search's HLO, as compiled for this backend: each instruction
    keeps the scope that emitted it in its ``op_name`` metadata."""
    params = index.default_params
    cap, mode = index.store.capacity, index.cfg.memory_mode.value
    if fetcher is None:
        low = search_mod.batch_search.lower(
            q, index.data, params, capacity=cap, mode=mode)
    else:
        fn = search_mod._stream_search_fn(fetcher, params, cap, mode)
        low = fn.lower(q, index.data, jnp.ones((q.shape[0],), bool))
    return low.compile().as_text()


@pytest.mark.parametrize("path", ["resident", "warmed", "streamed"])
def test_lowered_search_names_every_hop_stage_it_runs(path, corpus,
                                                      artifact):
    x, q = corpus
    if path == "resident":
        index = PageANNIndex.build(x, _cfg())
        want = STAGES - {"hop_fetch", "hop_cache_probe"}
    elif path == "warmed":
        index = load_index(artifact)
        assert index.data.cached_pages.shape[0] > 0
        want = STAGES - {"hop_fetch"}
    else:
        index = load_index(artifact, memory_budget=MemoryBudget(fraction=0.5))
        want = STAGES
    hlo = _hlo(index, jnp.asarray(q), fetcher=index.fetcher)
    assert _scopes(hlo) == want
    if path == "streamed":
        # the host callback, and nothing else, runs under hop_fetch
        fetch_ops = re.findall(r'op_name="[^"]*/hop_fetch/([^"]*)"', hlo)
        assert fetch_ops and set(fetch_ops) == {"pure_callback"}


@pytest.mark.parametrize("mode", [MemoryMode.HYBRID, MemoryMode.DISK_ONLY],
                         ids=lambda m: m.value)
def test_shared_reads_match_the_profiled_trail(mode, corpus):
    """A read is shared when a lower-numbered lane read the same page at
    the same hop; counted here in numpy from the profiled per-hop pages."""
    x, q = corpus
    index = PageANNIndex.build(x, _cfg(mode))
    kw = dict(capacity=index.store.capacity, mode=mode.value)
    q = jnp.asarray(q)
    res = search_mod.batch_search(q, index.data, index.default_params, **kw)
    _, trail = search_mod.profile_search(
        q, index.data, index.default_params, **kw)
    pages = np.asarray(trail.pages)                 # (Q, H, b)
    want = np.zeros(pages.shape[0], np.int64)
    for h in range(pages.shape[1]):
        earlier: set[int] = set()
        for i in range(pages.shape[0]):
            read = [int(p) for p in pages[i, h] if p >= 0]
            want[i] += sum(p in earlier for p in read)
            earlier.update(read)
    got = np.asarray(res.shared_reads)
    np.testing.assert_array_equal(got, want)
    # the repeated half of the batch reads only what its twin read
    reads = np.asarray(res.ios) + np.asarray(res.cache_hits)
    np.testing.assert_array_equal(reads, (pages >= 0).sum((1, 2)))
    half = len(got) // 2
    np.testing.assert_array_equal(got[half:], reads[half:])


def test_shared_read_count_stays_linear_in_the_batch():
    """``PageANNIndex.search`` hands a whole query set to one call: at 4,096
    lanes the count holds no array past a few times the trail (a compare
    of each hop's reads with all others would hold Q*b times it) and
    matches a numpy count."""
    q, h, b, pages = 4096, 64, 5, 3334
    rng = np.random.default_rng(0)
    # b distinct pages a lane and hop, crowded low so lanes share; PAD
    # once a lane has stopped
    first = rng.integers(0, pages // 8, (q, h, 1))
    trail = (first + np.arange(b) * 419) % pages
    stop = rng.integers(1, h, (q, 1, 1))
    trail = np.where(np.arange(h)[None, :, None] < stop, trail,
                     search_mod.PAD).astype(np.int32)

    jaxpr = jax.make_jaxpr(search_mod._shared_reads)(trail)
    sizes = [v.aval.size for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars]
    assert max(sizes) <= 4 * trail.size

    want = np.zeros(q, np.int64)
    for hop in range(h):
        reads = trail[:, hop].reshape(-1)               # lane-major
        _, firsts = np.unique(reads, return_index=True)
        shared = np.ones(reads.size, bool)
        shared[firsts] = False
        shared &= reads != search_mod.PAD
        want += shared.reshape(q, b).sum(1)
    got = np.asarray(jax.jit(search_mod._shared_reads)(trail))
    np.testing.assert_array_equal(got, want)
    assert 0 < got.sum() < (trail >= 0).sum()


def test_engine_counts_hops_and_page_reads(corpus):
    x, q = corpus
    index = PageANNIndex.build(x, _cfg())
    want = index.search(q, k=10)
    with BatchingEngine.from_index(index, batch_size=len(q)) as eng:
        for f in [eng.submit(row, k=10) for row in q]:
            f.result(timeout=60)
        m = eng.metrics()
    assert m.hops_total == int(np.sum(want.hops))
    assert m.hop_page_reads == int(np.sum(want.ios) + np.sum(want.cache_hits))
    assert m.hop_shared_reads == int(np.sum(want.shared_reads))
    assert 0 < m.hop_shared_reads < m.hop_page_reads


def test_phase_records_into_an_enabled_tracer_under_its_name():
    t = {"v": 1.0}
    tr = Tracer(clock=lambda: t["v"])
    with phase("engine.demux", tr, cat="engine", track="engine",
               batch_index=3) as span:
        t["v"] = 1.25
        span.annotate(n=2)
    (s,) = tr.spans()
    assert (s.name, s.ts, s.dur, s.track) == ("engine.demux", 1.0, 0.25,
                                              "engine")
    assert s.args == {"batch_index": 3, "n": 2}
    assert (span.t0, span.t1) == (1.0, 1.25)
    for off in (None, Tracer(enabled=False)):
        with phase("engine.demux", off) as span:
            pass
        assert span.t0 is None


def _host_events(log_dir: str) -> dict[str, list]:
    sys.path.insert(0, str(ROOT))
    from bench import xspace

    (f,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out: dict[str, list] = {}
    for plane in xspace.read(f).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(e)
    return out


def test_serving_spans_land_in_a_profiler_trace(corpus, artifact, tmp_path):
    """One HTTP /search of a streamed collection under the profiler, with
    no tracer anywhere: the frontend's, the engine's and the host fetch's
    spans are in the trace's host plane, nested in time."""
    x, q = corpus
    body = json.dumps({"collection": "c", "k": 10,
                       "queries": q.tolist()}).encode()
    with VectorService(batch_size=len(q)) as svc:
        svc.attach("c", artifact, memory_budget=MemoryBudget(fraction=0.5),
                   params=SearchParams(k=10, beam_width=32, max_hops=24))
        with HttpFrontend(svc, port=0) as fe:
            def post():
                req = urllib.request.Request(
                    f"{fe.url}/search", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as r:
                    assert r.status == 200
                    return json.loads(r.read())

            post()                                  # compiles
            jax.profiler.start_trace(str(tmp_path))
            try:
                got = post()
            finally:
                jax.profiler.stop_trace()
    assert len(got["results"]) == len(q)
    ev = _host_events(str(tmp_path))
    names = ("http.decode", "engine.assemble", "engine.dispatch",
             "engine.demux", "http.encode")
    for name in names:
        assert len(ev.get(name, ())) == 1, (name, sorted(ev))
    seq = [ev[n][0] for n in names]
    for a, b in zip(seq, seq[1:]):
        assert a.end_ns <= b.start_ns, (a.name, b.name)
    dispatch = ev["engine.dispatch"][0]
    fetches = ev.get("fetch.page_fetch", [])
    assert fetches
    for f in fetches:
        assert dispatch.start_ns <= f.start_ns <= f.end_ns <= dispatch.end_ns
    assert ev["http.decode"][0].stats["request"] == \
        ev["http.encode"][0].stats["request"]
    assert dispatch.stats["batch_index"] == \
        ev["engine.assemble"][0].stats["batch_index"]
    assert "misses" in fetches[0].stats
