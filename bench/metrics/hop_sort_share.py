"""% of the chip's busy time in the hop's sort and probe stages."""
from bench import trace_reduce

# the stages built on sorts, searchsorted probes and top-k selections
SORT_STAGES = r"\bhop_(?:select|cache_probe|cand_probe|dedupe|merge)\b"
ANY_STAGE = r"\bhop_[a-z_]+\b"


def compute(rec: dict):
    """Self seconds of the operations whose JAX scope holds
    ``hop_select``, ``hop_cache_probe``, ``hop_cand_probe``, ``hop_dedupe``
    or ``hop_merge``, averaged over the chips, over the seconds in which
    any operation ran (``busy_s`` averages too); None where no operation
    carries a hop stage's scope."""
    tr = rec["trace"]
    if tr is None or not trace_reduce.op_seconds(tr, ANY_STAGE)[1]:
        return None
    secs, _ = trace_reduce.op_seconds(tr, SORT_STAGES)
    return 100.0 * secs / len(tr.device) / trace_reduce.busy_s(tr)
