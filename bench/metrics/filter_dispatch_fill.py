"""Filtered queries per filtered dispatch over the window."""


def compute(rec: dict):
    """The engine's ``filtered_queries`` over its ``filtered_dispatches``,
    differenced across the window: how many of a dispatch's rows were
    filtered requests, where predicates of one shape and path (and, on
    the graph path, beam factor) share a dispatch. None where the program
    keeps no such counters or no filtered dispatch ran."""
    c = rec["counters"]
    if "filtered_dispatches" not in c["end"]:
        return None
    n = c["end"]["filtered_dispatches"] - c["start"]["filtered_dispatches"]
    if not n:
        return None
    return (c["end"]["filtered_queries"]
            - c["start"]["filtered_queries"]) / n
