"""What the per-layer metrics read, shared by their files in metrics/.

Each reader maps a run's record to one number, or None where the record
holds nothing for it (then the run leaves the metric out). The record:

  seconds             length of the window between the counter snapshots
  counters            {"start", "end"}: ``EngineMetrics`` of the service
                      at the window's edges, as dicts
  trace_counters      the same at the traced span's edges (traced runs)
  trace               ``trace_reduce.Trace`` of the traced span, or None
  compiles_in_window  executables JAX built inside the window
  record_bytes        bytes of one packed page record on the device
  peaks               the chip's entry of peaks.json
"""
from __future__ import annotations

from bench import trace_reduce

# the page scan's Pallas calls carry no name of their own (the trace names
# each after the loop that holds it), so they are found by their scope
PAGE_SCAN = r"jit\(page_scan(_recs)?\)/.*pallas_call"


def _delta(counters: dict, key: str) -> float:
    return counters["end"][key] - counters["start"][key]


def _ios(counters: dict, edge: str) -> float:
    """Pages read by every request answered so far (mean x count)."""
    m = counters[edge]
    return m["mean_ios"] * m["requests"]


def compiles_in_window(rec: dict):
    return rec["compiles_in_window"]


def pages_per_query(rec: dict):
    """Page records read per answered query (the program's ``ios``)."""
    c = rec["counters"]
    done = _delta(c, "requests")
    return (_ios(c, "end") - _ios(c, "start")) / done if done else None


def _streamed(c: dict) -> bool:
    """Whether the host fetch ran in the window (hits of its staging
    cache included): a fully resident collection never calls it."""
    return bool(_delta(c, "pages_fetched") + _delta(c, "fetch_hits"))


def fetch_wall_share(rec: dict):
    """Seconds spent inside the host page fetch, summed over its calls, as
    a % of the window's: calls of dispatches that overlap each count, so
    it passes 100 where the host fetches for several batches at once."""
    c = rec["counters"]
    if not _streamed(c):
        return None
    return 100.0 * _delta(c, "fetch_wall_s") / rec["seconds"]


def pages_fetched_per_query(rec: dict):
    """Page records read from the host memmap per answered query (those
    its staging cache held are not read)."""
    c = rec["counters"]
    done = _delta(c, "requests")
    if not (_streamed(c) and done):
        return None
    return _delta(c, "pages_fetched") / done


def page_scan_roofline(rec: dict):
    """% of the HBM roofline the page-scan kernel reaches: the bytes of the
    page records the answered queries read in the traced span, at the
    chip's HBM bandwidth, over the kernel's device time in that span.
    Bytes bound it: a record is ~8 KB and its arithmetic (squared
    differences of a few members, PQ lookups of the neighbours) is a few
    thousand operations, far below the bf16 peak's share. The count holds
    while each query reads its own pages; a kernel that reads a page once
    for several queries needs distinct pages per hop counted instead."""
    tr = rec["trace"]
    if tr is None:
        return None
    secs, count = trace_reduce.op_seconds(tr, PAGE_SCAN)
    if not count:
        raise ValueError("no page-scan kernel in the trace")
    c = rec["trace_counters"]
    nbytes = (_ios(c, "end") - _ios(c, "start")) * rec["record_bytes"]
    return 100.0 * nbytes / rec["peaks"]["hbm_bytes_per_s"] / secs


def device_idle_share(rec: dict):
    """% of the traced span in which no operation ran on the chip."""
    tr = rec["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(tr) / tr.window_s)
