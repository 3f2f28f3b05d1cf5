"""% of the page-record slots the host fetch ships that hold a requested
page, over the traced span (streamed cells)."""


def compute(rec: dict):
    """Requested pages (read off the map, or hits of the staging cache)
    over the record slots shipped to the chip (``staged_slots``),
    differenced across the traced span: how full the buffers each hop's
    host callback returns are. None where the program keeps no
    ``staged_slots`` counter or the span shipped nothing."""
    c = rec.get("trace_counters")
    if c is None or "staged_slots" not in c["end"]:
        return None
    shipped = c["end"]["staged_slots"] - c["start"]["staged_slots"]
    if not shipped:
        return None
    asked = sum(c["end"][k] - c["start"][k]
                for k in ("pages_fetched", "fetch_hits"))
    return 100.0 * asked / shipped
