"""The per-layer metrics that read the hop loop's stages, its hop and
shared-page counters and the serving path's host spans, on a small
hand-built trace and on counter dicts."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, trace_reduce  # noqa: E402
from test_bench_trace import Event, Line, Plane, Profile  # noqa: E402

NS = 1e-9
BODY = "jit(fn)/vmap()/while/body"


def profile():
    """One chip, busy [100, 700] in a hop loop whose stages hold 540 ns of
    its 600 and [800, 900] after it; the host fetched twice inside the
    chip's 200 ns wait on it and once after the traced span, and served
    two /search requests whose spans the span's edges cut."""
    ops = [
        Event("while.1", 100, 700, [("tf_op", "jit(fn)/vmap()/while")]),
        Event("sort.2", 110, 160, [("tf_op", f"{BODY}/hop_select/sort")]),
        Event("closed_call.3", 160, 200, [("tf_op", f"{BODY}/hop_scan/"
              "jit(page_scan)/while/body/closed_call/pallas_call")]),
        Event("pure_callback.4", 200, 400,
              [("tf_op", f"{BODY}/hop_fetch/pure_callback")]),
        Event("fusion.5", 400, 500, [("tf_op", f"{BODY}/hop_cand_probe/"
                                      "jit(searchsorted)/while/body/gather")]),
        Event("fusion.6", 500, 550, [("tf_op", f"{BODY}/hop_dedupe/sort")]),
        Event("fusion.7", 550, 600, [("tf_op", f"{BODY}/hop_merge/top_k")]),
        Event("pq_adc.8", 600, 650,
              [("tf_op", f"{BODY}/hop_nbr_adc/jit(pq_adc)/pallas_call")]),
        Event("fusion.9", 800, 900, [("tf_op", "jit(fn)/sort")]),
    ]
    host = [
        Event("fetch.page_fetch", 220, 320),
        Event("fetch.page_fetch", 350, 380),
        Event("fetch.page_fetch", 950, 1000),
        Event("http.decode", 0, 120),
        Event("http.encode", 710, 790),
        Event("http.decode", 800, 860),
        Event("http.encode", 890, 1000),
        Event("http.decode", 950, 990),
    ]
    return Profile([
        Plane("/host:CPU", [Line("python", host)]),
        Plane("/device:TPU:0", [Line("XLA Ops", ops)]),
    ])


def counters(requests=64, hops=12, reads=50, shared=20):
    start = dict(requests=100, hops_total=1000, hop_page_reads=5000,
                 hop_shared_reads=1000)
    end = dict(requests=100 + requests,
               hops_total=1000 + requests * hops,
               hop_page_reads=5000 + requests * reads,
               hop_shared_reads=1000 + requests * shared)
    return {"start": start, "end": end}


def record(trace=True):
    return {
        "trace": (trace_reduce.from_profile(profile(), window_s=1000 * NS)
                  if trace else None),
        "trace_counters": counters(),
    }


def read(name, rec):
    return spec.metric_reader(name)(rec)


@pytest.mark.parametrize("name, want", [
    ("hops_per_query", 12.0),
    ("distinct_pages_per_query", 30.0),
    # select 50 + cand probe 100 + dedupe 50 + merge 50 of 700 busy
    ("hop_sort_share", 100.0 * 250 / 700),
    # the callback's 200 ns of the 1000 ns span
    ("host_wait_share", 20.0),
    # fetch spans cover 100 + 30 ns of the 200 ns wait
    ("fetch_in_wait_share", 65.0),
    # decode 20 + 60 and encode 80 + 10 ns inside [100, 900], 2 decodes
    ("frontend_ms_per_request", 1e3 * 170 * NS / 2),
])
def test_metric_reads_the_record(name, want):
    assert read(name, record()) == pytest.approx(want)


def test_distinct_pages_never_exceed_the_reads():
    rec = record()
    c = rec["trace_counters"]
    reads = ((c["end"]["hop_page_reads"] - c["start"]["hop_page_reads"])
             / (c["end"]["requests"] - c["start"]["requests"]))
    got = read("distinct_pages_per_query", rec)
    assert 0 <= got <= reads
    rec["trace_counters"] = counters(shared=0)
    assert read("distinct_pages_per_query", rec) == pytest.approx(reads)


@pytest.mark.parametrize("name", ["hops_per_query",
                                  "distinct_pages_per_query"])
def test_counter_metrics_find_nothing_without_the_counters(name):
    rec = record()
    # a program without the counters (the parent's), or a traced span that
    # answered nothing, or a run with no traced span
    rec["trace_counters"] = {
        edge: {"requests": 100 + i, "mean_ios": 1.0}
        for i, edge in enumerate(("start", "end"))}
    assert read(name, rec) is None
    rec["trace_counters"] = counters(requests=0)
    assert read(name, rec) is None
    rec["trace_counters"] = None
    assert read(name, rec) is None


@pytest.mark.parametrize("name", ["hop_sort_share", "host_wait_share",
                                  "fetch_in_wait_share",
                                  "frontend_ms_per_request"])
def test_trace_metrics_find_nothing_without_a_trace(name):
    assert read(name, record(trace=False)) is None


def _without(pred):
    rec = record()
    tr = rec["trace"]
    tr.device = [[ev for ev in chip if not pred(ev[3])]
                 for chip in tr.device]
    return rec


def test_stage_metrics_find_nothing_without_the_stages():
    """A program whose ops carry no hop scope: the parent's."""
    def plain(scope):
        return scope.replace("hop_", "")
    rec = record()
    rec["trace"].device = [[(n, s, e, plain(sc)) for n, s, e, sc in chip]
                           for chip in rec["trace"].device]
    for name in ("hop_sort_share", "host_wait_share",
                 "fetch_in_wait_share"):
        assert read(name, rec) is None


def test_fetch_metrics_find_nothing_on_a_resident_search():
    rec = _without(lambda scope: "hop_fetch" in scope)
    assert read("host_wait_share", rec) is None
    assert read("fetch_in_wait_share", rec) is None
    # the other stages still read
    assert read("hop_sort_share", rec) > 0


def test_host_span_metrics_find_nothing_without_their_spans():
    rec = record()
    rec["trace"].host = [ev for ev in rec["trace"].host
                         if not ev[0].startswith(("http.", "fetch."))]
    assert read("frontend_ms_per_request", rec) is None
    assert read("fetch_in_wait_share", rec) is None


def test_host_spans_are_clipped_to_the_chips_first_and_last_op():
    """The span's bounds are the clipped device ops' first start and last
    end: moving the last op moves what counts of the host's spans."""
    rec = record()
    tr = rec["trace"]
    tr.device = [[ev for ev in chip if ev[0] != "fusion.9"]
                 for chip in tr.device]
    # now [100, 700]: decode 20 only, encode none; 1 decode
    assert read("frontend_ms_per_request", rec) == pytest.approx(
        1e3 * 20 * NS)


@pytest.mark.parametrize("name", ["hop_sort_share", "host_wait_share"])
def test_chip_shares_read_the_same_on_more_chips(name):
    """Four chips doing one chip's work read as one chip does: the stage
    seconds summed over the chips are averaged, as the busy time is."""
    one = read(name, record())
    prof = profile()
    chip = prof.planes[1]
    prof.planes += [Plane(f"/device:TPU:{i}", chip.lines) for i in (1, 2, 3)]
    rec = record()
    rec["trace"] = trace_reduce.from_profile(prof, window_s=1000 * NS)
    assert len(rec["trace"].device) == 4
    assert read(name, rec) == pytest.approx(one)
