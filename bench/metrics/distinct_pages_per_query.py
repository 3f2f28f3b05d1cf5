"""Distinct pages per answered query: the least a hop kernel that reads
each page a hop shares across the batch once would read."""


def compute(rec: dict):
    """Page reads (``hop_page_reads``, cache hits included) less the reads
    of a page that a lower-numbered query of the same dispatch read at the
    same hop (``hop_shared_reads``), per answered query, differenced across
    the traced span; None where the program keeps no such counters or the
    span answered nothing."""
    c = rec.get("trace_counters")
    if c is None or "hop_shared_reads" not in c["end"]:
        return None
    done = c["end"]["requests"] - c["start"]["requests"]
    if not done:
        return None
    reads, shared = (c["end"][k] - c["start"][k]
                     for k in ("hop_page_reads", "hop_shared_reads"))
    return (reads - shared) / done
