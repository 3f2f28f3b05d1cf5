"""Per-query planning of filtered search: an exact scan of the vectors a
predicate's posting lists name, or the page graph with the predicate as a
member mask.

The graph cannot answer every predicate. A search reads at most
``max_hops * io_batch`` pages, so it sees at most ``reach = max_hops *
io_batch * capacity`` vectors; when M of N vectors pass a predicate
unrelated to the query, the k-th nearest of them ranks about ``k * N / M``
in the whole order, out of reach once M < ``k * N / reach``, whatever the
beam. A selective predicate is instead answered exactly: the posting
lists give its matches, and the device gathers and scores them
(``core.search.posting_scan``). Each query takes the path its own match
count M calls for, decided from what the index knows and nothing a user
sets:

  threshold = max(ceil(k N / reach),        the graph's reach
                  pages * rows_per_page,    the graph's page budget in rows
                  ceil(N / max_factor))     the widest beam that fits

where ``pages = max_hops * io_batch`` and ``rows_per_page`` is how many
vectors' bytes one page record holds (record bytes over vector bytes): at
or below the threshold the exact scan reads no more bytes than the graph
may, finds every match, and needs no beam. ``max_factor`` is the widest
power-of-two beam oversampling (within ``FilterParams``' cap) whose
select pass, an (L, L) compare per lane held as 4-byte words for a batch
of ``REFERENCE_BATCH`` queries, fits in a quarter of the device's memory.

A query with M <= threshold takes the exact path: its matches fill whole
rows of ``row_width(k)`` candidates (its bucket is that count of rows),
and every exact query of one filter shape shares one group, whose
dispatch packs its queries' rows back to back into one program sized by
the power of two at or above the group's rows (``packed_rows``). So a
request's selective predicates, whatever their match counts, take one
dispatch, and the device reads about M candidates a query, not a
power-of-two bucket for every lane of a batch. A query above the
threshold takes the graph, its beam widened by the power of two at or
above N / M (its bucket, capped by ``max_factor``); queries of one
(filter shape, graph, bucket) share one program and dispatch. The graph
path runs a group in programs of ``GRAPH_LANES`` queries: its vmapped hop
loop computes every lane on every hop, and a group of a few broad
predicates padded to a 64-query batch at beam 256 held most of the chip's
time on the filter track's cell (PERF.md, section 6).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

from repro.core.filter import CompiledFilter

EXACT, GRAPH = "exact", "graph"
# the serving engine's default batch: the width the fit of the widest
# oversampled dispatch is judged at
REFERENCE_BATCH = 64
# device memory assumed where the backend reports none (a TPU v5e's HBM)
DEFAULT_DEVICE_BYTES = 16 * 2**30
# queries a graph-path program runs at once (module docstring)
GRAPH_LANES = 16
# candidates an exact-path row holds at least, and the fewest rows an
# exact program packs (module docstring)
EXACT_ROW = 32
MIN_PACKED_ROWS = 64


def pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def row_width(k: int) -> int:
    """Candidates an exact-path row holds: the row's own top-k is taken
    first, so a row holds at least k."""
    return max(EXACT_ROW, pow2_at_least(k))


def packed_rows(rows: int) -> int:
    """Rows of the exact program that packs ``rows`` rows of candidates."""
    return max(MIN_PACKED_ROWS, pow2_at_least(rows))


class QueryPlan(NamedTuple):
    """One query's filter and the path chosen for it."""

    filter: CompiledFilter
    path: str                 # EXACT or GRAPH
    # EXACT: rows of ``row_width(k)`` candidates its matches fill;
    # GRAPH: beam factor
    bucket: int
    count: int                # M: vectors passing the filter
    # EXACT: the passing vectors' ids in the reassigned (page-slot) space,
    # in ascending original-id order; None on the graph path
    matches: np.ndarray | None = None

    @property
    def group(self) -> tuple:
        """What queries sharing one dispatch share: the filter shape and
        the path, and on the graph path its beam factor (the exact path's
        program is sized at dispatch by its group's rows)."""
        if self.path == EXACT:
            return (self.filter.shape, EXACT)
        return (self.filter.shape, GRAPH, self.bucket)


@dataclasses.dataclass(frozen=True)
class Planner:
    """What an index knows that the path choice depends on."""

    live: int            # vectors in the index (N)
    capacity: int        # vectors per page
    rows_per_page: int   # record bytes // vector bytes
    device_bytes: int = DEFAULT_DEVICE_BYTES

    def max_factor(self, beam: int, cap: int) -> int:
        """Widest power-of-two beam oversampling, at most ``cap``, whose
        select pass fits (module docstring)."""
        budget = self.device_bytes // 4
        f = 1
        while (2 * f <= cap
               and REFERENCE_BATCH * (beam * 2 * f) ** 2 * 4 <= budget):
            f *= 2
        return f

    def threshold(self, params, cap: int) -> int:
        """The largest match count the exact path takes (module
        docstring), for ``params`` (``SearchParams``)."""
        pages = params.max_hops * params.io_batch
        reach = pages * self.capacity
        return max(-(-params.k * self.live // reach),
                   pages * self.rows_per_page,
                   -(-self.live // self.max_factor(params.beam_width, cap)))

    def plan(self, cf: CompiledFilter, matches: np.ndarray,
             old_to_new: np.ndarray, params, cap: int,
             threshold: int | None = None) -> QueryPlan:
        """Route one query whose filter ``cf`` passes the original ids
        ``matches`` (ascending); ``threshold`` is
        ``self.threshold(params, cap)``, for a caller that plans many."""
        m = len(matches)
        if threshold is None:
            threshold = self.threshold(params, cap)
        if m <= threshold:
            return QueryPlan(cf, EXACT, -(-m // row_width(params.k)), m,
                             old_to_new[matches].astype(np.int32))
        return QueryPlan(cf, GRAPH, self.factor(m, params, cap), m)

    def slots(self, params, cap: int) -> int:
        """Rows an exact-path query can fill, as a power of two: the
        exact program's second top-k merges that many rows' first."""
        most = min(self.threshold(params, cap), self.live)
        return pow2_at_least(-(-most // row_width(params.k)))

    def factor(self, m: int, params, cap: int) -> int:
        """The graph path's beam oversampling for ``m`` matches: the power
        of two at or above N / m, within ``max_factor``."""
        return min(pow2_at_least(-(-self.live // max(m, 1))),
                   self.max_factor(params.beam_width, cap))

    def buckets(self, params, cap: int,
                batch: int) -> list[tuple[str, int]]:
        """Every program a dispatch of at most ``batch`` queries can run
        under ``params``, as (path, size): the exact path's by packed rows,
        from the fewest up to ``batch`` queries each filling its rows, the
        graph's by beam factor, from 1 up to the oversampling the least
        selective graph-path query needs."""
        t = self.threshold(params, cap)
        most = -(-min(t, self.live) // row_width(params.k))
        out = [(EXACT, r) for r in _pow2_range(packed_rows(1),
                                               packed_rows(batch * most))]
        if t < self.live:
            top = self.factor(t + 1, params, cap)
            out += [(GRAPH, f) for f in _pow2_range(1, top)]
        return out


def _pow2_range(lo: int, hi: int) -> list[int]:
    out = []
    while lo <= hi:
        out.append(lo)
        lo *= 2
    return out
