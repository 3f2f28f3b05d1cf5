"""The per-layer metrics of filtered search (``filter_dispatch_fill``,
``filter_mask_share``, ``posting_scan_roofline``) on a small hand-built
trace and on counter dicts."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, trace_reduce  # noqa: E402
from test_bench_trace import Event, Line, Plane, Profile  # noqa: E402

NS = 1e-9
BODY = "jit(batch_search)/vmap()/while/body"


def profile(mask=True):
    """One chip busy 450 ns of [100, 600]: a hop loop [100, 400] whose
    predicate stage holds 60 ns, and two exact scans [400, 450] and
    [500, 600]."""
    ops = [
        Event("while.1", 100, 400, [("tf_op", "jit(batch_search)/while")]),
        Event("fusion.2", 120, 200, [("tf_op", f"{BODY}/hop_scan/gather")]),
        Event("fusion.4", 300, 380, [("tf_op", f"{BODY}/hop_merge/top_k")]),
        Event("gather.5", 400, 450,
              [("tf_op", "jit(posting_scan)/posting_scan/gather")]),
        Event("fusion.6", 500, 600,
              [("tf_op", "jit(posting_scan)/posting_scan/reduce")]),
    ]
    if mask:
        ops.append(Event("fusion.3", 200, 260, [(
            "tf_op", f"{BODY}/hop_scan/hop_filter/eq")]))
    return Profile([Plane("/device:TPU:0", [Line("XLA Ops", ops)])])


def counters(queries=640, dispatches=20, posting_bytes=98280):
    start = dict(filtered_queries=100, filtered_dispatches=10,
                 posting_bytes=1000)
    end = dict(filtered_queries=100 + queries,
               filtered_dispatches=10 + dispatches,
               posting_bytes=1000 + posting_bytes)
    return {"start": start, "end": end}


def record(mask=True):
    return {
        "trace": trace_reduce.from_profile(profile(mask), window_s=700 * NS),
        "counters": counters(),
        "trace_counters": counters(),
        "peaks": {"hbm_bytes_per_s": 819e9},
    }


def read(name, rec):
    return spec.metric_reader(name)(rec)


@pytest.mark.parametrize("name, want", [
    ("filter_dispatch_fill", 32.0),                    # 640 / 20
    ("filter_mask_share", 100.0 * 60 / 450),           # 60 of 450 busy
    # 98,280 B at 819 GB/s is 120 ns of the scans' 150 ns
    ("posting_scan_roofline", 80.0),
])
def test_filter_metrics_read_the_record(name, want):
    assert read(name, record()) == pytest.approx(want)


def test_only_the_exact_path_reads_no_mask_time():
    assert read("filter_mask_share", record(mask=False)) == 0.0


@pytest.mark.parametrize("name", ["filter_dispatch_fill",
                                  "filter_mask_share",
                                  "posting_scan_roofline"])
def test_a_program_without_filtered_search_reads_nothing(name):
    """The parent's engine has no filtered counters and its trace no
    filtered scope: each reader returns None, and none raises."""
    rec = record()
    for key in ("counters", "trace_counters"):
        rec[key] = {edge: {"requests": 1} for edge in ("start", "end")}
    rec["trace"] = trace_reduce.from_profile(Profile([Plane(
        "/device:TPU:0", [Line("XLA Ops", [Event("fusion.1", 0, 10)])])]),
        window_s=10 * NS)
    assert read(name, rec) is None


def test_no_filtered_dispatch_in_the_window_reads_nothing():
    rec = record()
    rec["counters"] = counters(queries=0, dispatches=0)
    assert read("filter_dispatch_fill", rec) is None
