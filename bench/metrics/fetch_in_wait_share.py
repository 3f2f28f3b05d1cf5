"""% of the chip's wait on the host fetch spent inside the fetcher."""
import re

from bench import trace_reduce
from bench.metrics.frontend_ms_per_request import host_spans
from bench.metrics.host_wait_share import FETCH_STAGE


def _overlap(a: list, b: list) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def compute(rec: dict):
    """Seconds of the ``hop_fetch`` operations (the chip waiting on the
    host) during which the host was inside ``PageFetcher.__call__`` (a
    ``fetch.page_fetch`` span), over those operations' seconds; the rest of
    the wait is the callback's machinery and the copies around it. None
    where no operation runs in scope ``hop_fetch`` or no fetch span was
    recorded."""
    tr = rec["trace"]
    if tr is None:
        return None
    rx = re.compile(FETCH_STAGE)
    waits = [trace_reduce.merged([ev for ev in chip if rx.search(ev[3])])
             for chip in tr.device]
    wait_ns = sum(e - s for chip in waits for s, e in chip)
    if not wait_ns:
        return None
    fetch = trace_reduce.merged(
        [("", s, e) for s, e in host_spans(tr, "fetch.page_fetch")])
    if not fetch:
        return None
    return 100.0 * sum(_overlap(chip, fetch) for chip in waits) / wait_ns
