"""Each per-layer metric file of BENCHMARK.json on one fixed record."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec, trace_reduce  # noqa: E402
from test_bench_trace import profile  # noqa: E402

NAMES = [m["name"] for m in
         json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]]


def counters(**end):
    start = dict(requests=100, batches=10, mean_ios=20.0, pages_fetched=0,
                 fetch_hits=0, fetch_wall_s=0.0)
    return {"start": start, "end": dict(start, **end)}


def record():
    """640 queries in 10 batches over 10 s; 14,280 pages read, 6,400 of
    them fetched in 3 s; in the traced span one page of 12,288 bytes, read
    by a kernel that ran 20 ns of a 300 ns span busy for 150 ns."""
    return {
        "seconds": 10.0,
        "counters": counters(requests=740, batches=20, mean_ios=22.0,
                             pages_fetched=6400, fetch_wall_s=3.0),
        "trace_counters": {
            "start": dict(requests=0, mean_ios=0.0),
            "end": dict(requests=1, mean_ios=1.0),
        },
        "trace": trace_reduce.from_profile(profile(), window_s=300e-9),
        "compiles_in_window": 0,
        "record_bytes": 12288,
        "peaks": {"hbm_bytes_per_s": 819e9},
    }


EXPECTED = {
    "compiles_in_window": 0,
    "pages_per_query": 14280 / 640,
    "fetch_wall_share": 30.0,
    "pages_fetched_per_query": 10.0,
    # 12,288 B at 819 GB/s is 15.0 ns of the kernel's 20 ns
    "page_scan_roofline": 100.0 * 12288 / 819e9 / 20e-9,
    "device_idle_share": 50.0,
}


@pytest.mark.parametrize("name", NAMES)
def test_metric_file_reads_the_record(name):
    got = spec.metric_reader(name)(record())
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NAMES)
def test_metric_finds_nothing_in_an_empty_window(name):
    rec = record()
    rec["counters"] = counters()
    rec["trace"] = None
    got = spec.metric_reader(name)(rec)
    assert got is None or name.startswith("compiles_in_window")


def test_the_roofline_is_an_error_without_its_kernel():
    rec = record()
    rec["trace"].device[0] = [e for e in rec["trace"].device[0]
                              if not e[0].startswith("closed_call")]
    with pytest.raises(ValueError, match="no page-scan kernel"):
        spec.metric_reader("page_scan_roofline")(rec)
