"""``staged_slot_fill``: the share of the shipped record slots that hold
a requested page, from the engine's fetch counters at the traced span's
edges."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

START = dict(requests=100, pages_fetched=50, fetch_hits=10, fetch_calls=4,
             staged_slots=120)


def _rec(**end):
    edges = {"start": START, "end": dict(START, **end)}
    return {"seconds": 10.0, "counters": edges, "trace_counters": edges}


@pytest.mark.parametrize("end, want", [
    # 600 pages read and 120 staging hits in 900 shipped slots
    (dict(pages_fetched=650, fetch_hits=130, staged_slots=1020), 80.0),
    # hits alone fill the slots too
    (dict(fetch_hits=50, staged_slots=160), 100.0),
    # nothing shipped in the window: a resident index or no traffic
    (dict(), None),
])
def test_fill_is_requested_pages_over_shipped_slots(end, want):
    got = spec.metric_reader("staged_slot_fill")(_rec(**end))
    assert got == (None if want is None else pytest.approx(want))


def test_fill_is_none_without_the_counter():
    """The counters of a program that ships no packed buffer."""
    start = {k: v for k, v in START.items() if k != "staged_slots"}
    edges = {"start": start,
             "end": dict(start, pages_fetched=650, fetch_hits=130)}
    rec = {"seconds": 10.0, "counters": edges, "trace_counters": edges}
    assert spec.metric_reader("staged_slot_fill")(rec) is None


def test_fill_is_none_without_a_traced_span():
    """A run with no trace (``--trace 0``, or a CPU rehearsal) has no
    counters at a traced span's edges."""
    rec = _rec(pages_fetched=650, fetch_hits=130, staged_slots=1020)
    rec["trace_counters"] = None
    assert spec.metric_reader("staged_slot_fill")(rec) is None
