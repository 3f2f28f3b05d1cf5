"""Milliseconds of HTTP decode and encode per /search request."""
from bench import trace_reduce


def span_bounds(tr) -> tuple[int, int]:
    """The traced span, as the chip's clipped operations give it: the first
    start and the last end."""
    ops = [ev for chip in tr.device for ev in chip]
    return min(ev[1] for ev in ops), max(ev[2] for ev in ops)


def host_spans(tr, name: str) -> list[tuple[int, int]]:
    """The host events named ``name``, clipped to the traced span."""
    lo, hi = span_bounds(tr)
    return [(max(s, lo), min(e, hi)) for n, s, e in tr.host
            if n == name and e > lo and s < hi]


def compute(rec: dict):
    """Seconds in ``http.decode`` and ``http.encode`` spans inside the
    traced span, over the number of ``http.decode`` spans there, in ms;
    None where the trace holds no ``http.decode`` span."""
    tr = rec["trace"]
    if tr is None or not any(tr.device):
        return None
    decode = host_spans(tr, "http.decode")
    if not decode:
        return None
    spans = decode + host_spans(tr, "http.encode")
    return 1e3 * sum(e - s for s, e in spans) * trace_reduce.NS / len(decode)
