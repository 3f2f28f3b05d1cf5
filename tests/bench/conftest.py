"""What test_bench_metrics.py's fixed record gives for the per-layer
metrics that came after it.

That test reads every metric of BENCHMARK.json on one record and looks
each up in its ``EXPECTED``. Its record holds what the program reported
before the hop counters, the hop stages' scopes and the serving path's
host spans existed, so each of these metrics finds nothing there: its
reader returns None, which is what it must do on a program without them.
test_bench_hop_metrics.py reads the same metrics where the record has
them.
"""
import test_bench_metrics

test_bench_metrics.EXPECTED.update(dict.fromkeys((
    "hops_per_query", "distinct_pages_per_query", "hop_sort_share",
    "host_wait_share", "fetch_in_wait_share", "frontend_ms_per_request",
), None))
