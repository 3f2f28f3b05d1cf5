"""Hops of the search loop per answered query, over the traced span."""


def compute(rec: dict):
    """The engine's ``hops_total`` over its ``requests``, differenced across
    the traced span (the seconds the device trace covers); None where the
    program keeps no such counter or the span answered nothing."""
    c = rec.get("trace_counters")
    if c is None or "hops_total" not in c["end"]:
        return None
    done = c["end"]["requests"] - c["start"]["requests"]
    if not done:
        return None
    return (c["end"]["hops_total"] - c["start"]["hops_total"]) / done
