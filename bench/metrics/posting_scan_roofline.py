"""% of the HBM roofline the exact path's scan of posting-list matches
reaches."""
from bench import trace_reduce

SCOPE = r"\bposting_scan\b"


def scanned_bytes(counters: dict) -> float:
    """Bytes of the candidate vectors the exact path gathered and scored
    between two engine snapshots: the candidates of its dispatches'
    packed rows, padding included, times the vector's bytes, dim x 4
    for float32 (the engine's ``posting_bytes``). Each is read once from
    the page records and once more from the gathered block, so this
    counts the least the scan must move."""
    return counters["end"]["posting_bytes"] - counters["start"]["posting_bytes"]


def compute(rec: dict):
    """``scanned_bytes`` of the traced span at the chip's HBM bandwidth,
    over the self seconds of the operations in scope ``posting_scan``;
    None where the program keeps no such counter or no such operation
    ran in the span."""
    tr, c = rec["trace"], rec.get("trace_counters")
    if tr is None or c is None or "posting_bytes" not in c["end"]:
        return None
    secs, count = trace_reduce.op_seconds(tr, SCOPE)
    if not count:
        return None
    return (100.0 * scanned_bytes(c) / rec["peaks"]["hbm_bytes_per_s"]
            / secs)
