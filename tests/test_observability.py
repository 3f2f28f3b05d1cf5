"""Observability subsystem: span tracer, metrics exposition, engine
quantile/window accounting vs numpy oracles, per-hop search profiling.

Clock-sensitive tests inject a fake clock object (no sleeps): the engine
stamps ``t_submit`` at submit and ``t_done`` after the backend call, so a
backend that advances the fake clock by a chosen delta makes each
request's latency exactly that delta.
"""
import json
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MemoryMode, PageANNConfig, PageANNIndex, SearchParams
from repro.core import search as search_mod
from repro.core.search import PAD, SearchResult
from repro.data.pipeline import clustered_vectors, query_vectors
from repro.obs import (
    MetricsServer,
    Tracer,
    parse_prometheus_text,
    sample_value,
    serve_registry,
)
from repro.obs import report as report_mod
from repro.obs.metrics import MetricsRegistry
from repro.serve import BatchingEngine

N, D = 800, 32


# ------------------------------------------------------------------ tracer
def test_tracer_records_spans_in_order():
    t = {"v": 0.0}
    tr = Tracer(clock=lambda: t["v"])
    t["v"] = 1.0
    with tr.span("phase_a", cat="x", track="eng", n=3):
        t["v"] = 1.5
    tr.add("phase_b", 2.0, 2.25, track="req-1", args={"k": 10})
    tr.instant("marker")
    spans = tr.spans()
    assert [s.name for s in spans] == ["phase_a", "phase_b", "marker"]
    a, b, m = spans
    assert (a.ts, a.dur, a.track, a.args) == (1.0, 0.5, "eng", {"n": 3})
    assert (b.ts, b.dur) == (2.0, 0.25)
    assert m.dur == 0.0
    assert len(tr) == 3 and tr.dropped == 0


def test_tracer_disabled_is_noop_and_shares_null_span():
    tr = Tracer(enabled=False)
    s1 = tr.span("a")
    s2 = tr.span("b")
    assert s1 is s2  # one shared no-op CM, no per-call allocation
    with s1:
        pass
    tr.add("c", 0.0, 1.0)
    tr.instant("d")
    assert len(tr) == 0 and tr.spans() == []


def test_tracer_ring_buffer_drops_oldest_and_counts():
    tr = Tracer(capacity=4, clock=lambda: 0.0)
    for i in range(7):
        tr.add(f"s{i}", float(i), float(i))
    assert len(tr) == 4
    assert tr.dropped == 3
    assert [s.name for s in tr.spans()] == ["s3", "s4", "s5", "s6"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_negative_duration_clamps_to_zero():
    tr = Tracer()
    tr.add("backwards", 5.0, 4.0)
    assert tr.spans()[0].dur == 0.0


def test_chrome_export_structure(tmp_path):
    tr = Tracer(clock=lambda: 0.0)
    tr.add("first", 10.0, 10.002, cat="engine", track="engine")
    tr.add("second", 10.001, 10.004, track="req-1", args={"k": 5})
    doc = json.loads(tr.to_chrome_json())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    body = [e for e in events if e["ph"] == "X"]
    # one process_name + one thread_name per distinct track
    assert {e["args"]["name"] for e in meta} == {
        "repro-serve", "engine", "req-1"
    }
    # timestamps are microseconds relative to the EARLIEST span
    first = next(e for e in body if e["name"] == "first")
    second = next(e for e in body if e["name"] == "second")
    assert first["ts"] == 0.0 and first["dur"] == pytest.approx(2000.0)
    assert second["ts"] == pytest.approx(1000.0)
    assert second["args"] == {"k": 5}
    # distinct tracks get distinct tids
    assert first["tid"] != second["tid"]
    out = tmp_path / "trace.json"
    tr.save(str(out))
    assert json.loads(out.read_text()) == doc


# ---------------------------------------------------------------- registry
def test_registry_counter_gauge_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "req")
    c.inc()
    c.inc(4.0)
    reg.gauge("t_qps", "qps").set(123.5)
    parsed = parse_prometheus_text(reg.render())
    assert sample_value(parsed, "t_requests_total") == 5.0
    assert sample_value(parsed, "t_qps") == 123.5
    # create-or-get returns the same family; kind mismatch raises
    assert reg.counter("t_requests_total", "req") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("t_requests_total", "req")


def test_registry_histogram_buckets_sum_count():
    reg = MetricsRegistry()
    h = reg.histogram("t_lat_ms", "lat", buckets=(1.0, 5.0, 10.0))
    for v in (0.5, 0.7, 3.0, 7.0, 50.0):
        h.observe(v)
    parsed = parse_prometheus_text(reg.render())
    assert sample_value(parsed, "t_lat_ms_bucket", le="1") == 2
    assert sample_value(parsed, "t_lat_ms_bucket", le="5") == 3
    assert sample_value(parsed, "t_lat_ms_bucket", le="10") == 4
    assert sample_value(parsed, "t_lat_ms_bucket", le="+Inf") == 5
    assert sample_value(parsed, "t_lat_ms_sum") == pytest.approx(61.2)
    assert sample_value(parsed, "t_lat_ms_count") == 5
    # observe_window REPLACES the distribution rather than accumulating
    h.observe_window([2.0, 2.0])
    parsed = parse_prometheus_text(reg.render())
    assert sample_value(parsed, "t_lat_ms_count") == 2
    assert sample_value(parsed, "t_lat_ms_bucket", le="5") == 2


def test_registry_labels_and_validation():
    reg = MetricsRegistry()
    g = reg.gauge("t_pages", "pages")
    g.set(7, labels={"collection": 'we"ird'})
    g.set(9, labels={"collection": "other"})
    parsed = parse_prometheus_text(reg.render())
    assert sample_value(parsed, "t_pages", collection='we"ird') == 7
    assert sample_value(parsed, "t_pages", collection="other") == 9
    with pytest.raises(KeyError):
        sample_value(parsed, "t_pages", collection="absent")
    with pytest.raises(ValueError):
        reg.counter("bad name", "x")
    with pytest.raises(ValueError):
        reg.histogram("t_h", "x", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        parse_prometheus_text("t_ok 1\nthis is not a sample line !!\n")


# --------------------------------------- engine accounting vs numpy oracles
class _FakeClock:
    """Deterministic monotonic clock; tests advance ``.t`` explicitly."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _clocked_backend(clock, latencies_s, hops_list, ios=3):
    """Per-dispatch backend: advances the fake clock by the next latency
    (so request latency == that delta at batch_size=1) and reports the
    next scripted hop count."""
    lat_it = iter(latencies_s)
    hop_it = iter(hops_list)

    def fn(q, k, params):
        clock.t += next(lat_it)
        b = q.shape[0]
        return SearchResult(
            ids=jnp.zeros((b, k), jnp.int32),
            dists=jnp.zeros((b, k), jnp.float32),
            ios=jnp.full((b,), ios, jnp.int32),
            hops=jnp.full((b,), next(hop_it), jnp.int32),
            cache_hits=jnp.zeros((b,), jnp.int32),
        )

    return fn


def test_latency_and_hops_quantiles_match_numpy_oracle():
    rng = np.random.default_rng(7)
    lat_s = rng.uniform(0.001, 0.2, size=100)
    hops = rng.integers(1, 40, size=100)
    clock = _FakeClock()
    eng = BatchingEngine(
        _clocked_backend(clock, lat_s, hops), dim=4, batch_size=1,
        clock=clock,
    )
    for _ in range(100):
        eng.submit(np.zeros(4, np.float32)).result(timeout=30)
    m = eng.metrics()
    lat_ms = lat_s * 1e3
    assert m.requests == 100 and m.batches == 100
    assert m.latency_ms_mean == pytest.approx(lat_ms.mean())
    assert m.latency_ms_p50 == pytest.approx(np.percentile(lat_ms, 50))
    assert m.latency_ms_p99 == pytest.approx(np.percentile(lat_ms, 99))
    assert m.mean_hops == pytest.approx(hops.mean())
    assert m.p99_hops == pytest.approx(np.percentile(hops, 99))
    assert m.mean_ios == 3.0 and m.p99_ios == 3.0
    # windows snapshot must agree with the gauges' source data
    win = eng.metrics_windows()
    np.testing.assert_allclose(win["latency_ms"], lat_ms)
    np.testing.assert_array_equal(win["hops"], hops)
    eng.close()


def test_latency_window_evicts_oldest_at_overflow():
    window = 16
    total = 50
    lat_s = np.linspace(0.001, 0.05, total)
    hops = np.arange(1, total + 1)
    clock = _FakeClock()
    eng = BatchingEngine(
        _clocked_backend(clock, lat_s, hops), dim=4, batch_size=1,
        clock=clock, latency_window=window,
    )
    for _ in range(total):
        eng.submit(np.zeros(4, np.float32)).result(timeout=30)
    m = eng.metrics()
    # cumulative counters keep the full history ...
    assert m.requests == total
    # ... while the quantile gauges see exactly the trailing window
    tail_ms = lat_s[-window:] * 1e3
    assert m.latency_ms_mean == pytest.approx(tail_ms.mean())
    assert m.latency_ms_p50 == pytest.approx(np.percentile(tail_ms, 50))
    assert m.latency_ms_p99 == pytest.approx(np.percentile(tail_ms, 99))
    assert m.mean_hops == pytest.approx(hops[-window:].mean())
    win = eng.metrics_windows()
    assert len(win["latency_ms"]) == window
    np.testing.assert_allclose(win["latency_ms"], tail_ms)
    eng.close()


def test_early_exit_accounting_against_resolved_max_hops():
    hops = [3, 10, 10, 7, 10, 1]  # 3 requests exit before max_hops=10
    clock = _FakeClock()
    eng = BatchingEngine(batch_size=1, clock=clock)
    eng.add_collection(
        "c",
        _clocked_backend(clock, [0.001] * len(hops), hops),
        dim=4,
        default_k=5,
        resolve_fn=lambda k, p: SearchParams(k=k, max_hops=10),
    )
    for _ in range(len(hops)):
        eng.submit(np.zeros(4, np.float32), collection="c").result(timeout=30)
    assert eng.metrics().early_exits == 3
    eng.close()


# ---------------------------------------------------- exposition over engine
def test_serve_registry_reconciles_with_engine_metrics():
    rng = np.random.default_rng(3)
    n = 40
    lat_s = rng.uniform(0.001, 0.05, size=n)
    hops = rng.integers(1, 30, size=n)
    clock = _FakeClock()
    eng = BatchingEngine(
        _clocked_backend(clock, lat_s, hops), dim=4, batch_size=1,
        clock=clock,
    )
    for _ in range(n):
        eng.submit(np.zeros(4, np.float32)).result(timeout=30)
    reg = serve_registry(eng)
    parsed = parse_prometheus_text(reg.render())
    m = eng.metrics()
    assert sample_value(parsed, "pageann_requests_total") == m.requests
    assert sample_value(parsed, "pageann_batches_total") == m.batches
    assert sample_value(parsed, "pageann_early_exits_total") == m.early_exits
    assert sample_value(parsed, "pageann_compile_misses_total") == (
        m.compile_misses
    )
    assert sample_value(parsed, "pageann_latency_ms_p99") == pytest.approx(
        m.latency_ms_p99
    )
    assert sample_value(parsed, "pageann_mean_hops") == pytest.approx(
        m.mean_hops
    )
    assert sample_value(parsed, "pageann_collections") == 1
    # the latency histogram is the engine's trailing window verbatim
    assert sample_value(
        parsed, "pageann_request_latency_ms_count"
    ) == n
    assert sample_value(
        parsed, "pageann_request_latency_ms_sum"
    ) == pytest.approx((lat_s * 1e3).sum())
    assert sample_value(
        parsed, "pageann_request_hops_bucket", le="+Inf"
    ) == n
    eng.close()


def test_metrics_server_scrape_endpoints():
    clock = _FakeClock()
    eng = BatchingEngine(
        _clocked_backend(clock, [0.002] * 5, [4] * 5), dim=4, batch_size=1,
        clock=clock,
    )
    for _ in range(5):
        eng.submit(np.zeros(4, np.float32)).result(timeout=30)
    reg = serve_registry(eng)
    with MetricsServer(reg, source=eng) as srv:
        assert srv.port > 0
        with urllib.request.urlopen(f"{srv.url}/healthz", timeout=10) as r:
            assert r.status == 200 and r.read() == b"ok\n"
        with urllib.request.urlopen(f"{srv.url}/metrics", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            parsed = parse_prometheus_text(r.read().decode())
        assert sample_value(parsed, "pageann_requests_total") == 5
        with urllib.request.urlopen(f"{srv.url}/stats", timeout=10) as r:
            doc = json.loads(r.read().decode())
        assert doc["metrics"]["requests"] == 5
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{srv.url}/nope", timeout=10)
    eng.close()


# ------------------------------------------------- engine tracing integration
def test_engine_emits_expected_span_phases():
    clock = _FakeClock()
    tr = Tracer(clock=clock)
    eng = BatchingEngine(
        _clocked_backend(clock, [0.004] * 4, [5] * 4), dim=4, batch_size=2,
        clock=clock, tracer=tr,
    )
    futs = [eng.submit(np.zeros(4, np.float32)) for _ in range(4)]
    for f in futs:
        f.result(timeout=30)
    names = {s.name for s in tr.spans()}
    assert {
        "submit", "queue_wait", "engine.assemble", "engine.dispatch",
        "engine.demux", "request",
    } <= names
    # per-request spans live on per-request tracks; the first dispatch is
    # cold, and its span says so in its ``compiled`` argument
    reqs = [s for s in tr.spans() if s.name == "request"]
    assert sorted(s.track for s in reqs) == [
        "req-1", "req-2", "req-3", "req-4"
    ]
    dispatches = [s for s in tr.spans() if s.name == "engine.dispatch"]
    assert [d.args["compiled"] for d in dispatches] == [True, False]
    assert [d.args["batch_index"] for d in dispatches] == [0, 1]
    # request span duration equals the engine-reported latency
    for s in reqs:
        assert s.dur * 1e3 == pytest.approx(s.args["latency_ms"])
    eng.close()


# ------------------------------------------------------- per-hop profiling
@pytest.fixture(scope="module")
def small_index():
    x = clustered_vectors(N, D, num_clusters=16, seed=0)
    cfg = PageANNConfig(
        dim=D, graph_degree=12, build_beam=24, pq_subspaces=8,
        lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
        memory_mode=MemoryMode.HYBRID,
    )
    return PageANNIndex.build(x, cfg)


def test_tracing_leaves_served_results_bit_identical(small_index):
    """Tracing never changes the compiled program or the dispatch order:
    one query set served with no tracer, a disabled tracer and an enabled
    one returns the same ids, distances (to the bit), I/Os and hops."""
    x = clustered_vectors(N, D, num_clusters=16, seed=0)
    q = query_vectors(x, 20, seed=11)      # 8 + 8 + a partial batch of 4
    tracers = {"plain": None, "off": Tracer(enabled=False), "on": Tracer()}
    served = {}
    for name, tr in tracers.items():
        eng = BatchingEngine.from_index(
            small_index, k=10, batch_size=8, tracer=tr
        )
        try:
            rows = eng.search(q)
        finally:
            eng.close()
        served[name] = {
            f: np.stack([np.asarray(getattr(r.result, f)) for r in rows])
            for f in ("ids", "dists", "ios", "hops")
        }
    assert len(tracers["on"]) > 0 and len(tracers["off"]) == 0
    want = served["plain"]
    for name in ("off", "on"):
        for f, arr in served[name].items():
            ref = want[f].view(np.uint32) if f == "dists" else want[f]
            got = arr.view(np.uint32) if f == "dists" else arr
            np.testing.assert_array_equal(ref, got, err_msg=f"{name}: {f}")


@pytest.mark.parametrize("mode", list(MemoryMode))
def test_profile_search_matches_batch_search(mode):
    x = clustered_vectors(N, D, num_clusters=16, seed=0)
    cfg = PageANNConfig(
        dim=D, graph_degree=12, build_beam=24, pq_subspaces=8,
        lsh_sample=256, lsh_entries=8, beam_width=48, max_hops=48,
        memory_mode=mode,
    )
    index = PageANNIndex.build(x, cfg)
    q = jnp.asarray(query_vectors(x, 8, seed=5), jnp.float32)
    params = index.resolve_params(10, None)
    want = search_mod.batch_search(
        q, index.data, params, capacity=index.store.capacity,
        mode=mode.value,
    )
    got, trail = search_mod.profile_search(
        q, index.data, params, capacity=index.store.capacity,
        mode=mode.value,
    )
    # the profiled program reuses the same pure hop transitions: results
    # are identical, distances to the bit
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    assert np.array_equal(
        np.asarray(want.dists).view(np.uint32),
        np.asarray(got.dists).view(np.uint32),
    )
    np.testing.assert_array_equal(np.asarray(want.ios), np.asarray(got.ios))
    np.testing.assert_array_equal(np.asarray(want.hops), np.asarray(got.hops))
    np.testing.assert_array_equal(
        np.asarray(want.cache_hits), np.asarray(got.cache_hits)
    )
    # trail invariants: per-hop deltas sum to the totals, inactive hops
    # are fully frozen (no pages scheduled, no I/O)
    active = np.asarray(trail.active)
    np.testing.assert_array_equal(active.sum(1), np.asarray(got.hops))
    np.testing.assert_array_equal(
        np.asarray(trail.ios).sum(1), np.asarray(got.ios)
    )
    np.testing.assert_array_equal(
        np.asarray(trail.cache_hits).sum(1), np.asarray(got.cache_hits)
    )
    pages = np.asarray(trail.pages)
    assert (pages[~active] == PAD).all()
    assert (np.asarray(trail.ios)[~active] == 0).all()


def test_index_profile_api(tmp_path, small_index):
    x = clustered_vectors(N, D, num_clusters=16, seed=0)
    q = query_vectors(x, 4, seed=9)
    want = small_index.search(q, k=10)
    out = tmp_path / "profile.json"
    res, trail = small_index.profile(q, k=10, save=str(out))
    # translated ids match the fast path exactly
    np.testing.assert_array_equal(want.ids, res.ids)
    assert trail.pages.shape[0] == 4
    doc = json.loads(out.read_text())
    assert doc["kind"] == "pageann_profile"
    assert len(doc["ids"]) == 4
    # the report CLI renders it
    assert report_mod.main([str(out), "--queries", "2"]) == 0


def test_profile_rejects_streamed_index(small_index):
    class _Streamed(PageANNIndex):
        pass

    streamed = object.__new__(_Streamed)
    streamed.__dict__.update(small_index.__dict__)
    streamed.fetcher = object()
    with pytest.raises(ValueError, match="streamed"):
        streamed.profile(np.zeros((1, D), np.float32))


def test_report_cli_renders_chrome_trace(tmp_path, capsys):
    tr = Tracer(clock=lambda: 0.0)
    tr.add("device_dispatch", 0.0, 0.010, cat="engine", track="engine")
    tr.add("queue_wait", 0.0, 0.002, track="req-1")
    path = tmp_path / "trace.json"
    tr.save(str(path))
    assert report_mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "device_dispatch" in out and "queue_wait" in out
