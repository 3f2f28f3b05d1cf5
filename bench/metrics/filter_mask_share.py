"""% of the chip's busy time evaluating predicates in the hop loop."""
from bench import trace_reduce

MASK = r"\bhop_filter\b"
# the scopes of filtered work: the graph path's mask, the exact path
FILTERED = r"\b(?:hop_filter|posting_scan)\b"


def compute(rec: dict):
    """Self seconds of the operations in scope ``hop_filter`` (the graph
    path's per-hop predicate over a hop's pages), averaged over the chips,
    over the seconds in which any operation ran; 0 where only the exact
    path ran. None where no operation carries a filtered path's scope."""
    tr = rec["trace"]
    if tr is None or not trace_reduce.op_seconds(tr, FILTERED)[1]:
        return None
    secs, _ = trace_reduce.op_seconds(tr, MASK)
    return 100.0 * secs / len(tr.device) / trace_reduce.busy_s(tr)
