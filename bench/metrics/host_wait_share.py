"""% of the traced span the chip spends waiting on the host page fetch."""
from bench import trace_reduce

FETCH_STAGE = r"\bhop_fetch\b"


def compute(rec: dict):
    """Self seconds of the operations in scope ``hop_fetch`` (the streamed
    hop's ``pure_callback``: the chip holds while the host fetches), over
    the traced span, averaged over the chips; None where no operation runs
    in that scope."""
    tr = rec["trace"]
    if tr is None:
        return None
    secs, count = trace_reduce.op_seconds(tr, FETCH_STAGE)
    if not count:
        return None
    return 100.0 * secs / len(tr.device) / tr.window_s
