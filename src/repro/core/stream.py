"""Host-side streaming page tier: the memmap as the source of truth.

The paper's regime is disk-resident search under a memory budget; this
module is the half of it that lives on the host. A :class:`PageFetcher`
wraps the ``np.memmap`` of ``pages.bin`` and serves per-hop record
requests from the jitted search loop, one host round trip per hop for
the whole vmapped query batch (``core.search._fetch_hook``):

  * calling the fetcher with page ids of any shape returns their
    records, ``PAD``/-1 slots (resident pages, unselected lanes) zeroed
    without touching the file. The search packs a hop's live requests on
    the device and calls it with a (C,) array, live ids at the front and
    ``PAD`` behind them, C the smallest rung of a ladder that holds them;
    such a request is filled in place with no full-size zero buffer, and
    the device expands the records to the lanes' slots. A hop whose pages
    are all resident makes no call;
  * one vectorised gather serves a call: its distinct ids are split into
    staged hits and misses, the misses read with one fancy index of the
    map, with no loop over slots;
  * a bounded LRU **staging cache** of recently fetched records absorbs
    the re-reads a beam search naturally produces (the same hub pages are
    requested hop after hop, query after query). Each live slot counts
    one hit or one miss: a page repeated in one call is read once, a miss,
    and its repeats are hits. Its state is arrays (a page's slot, a slot's
    owner and last use), and one ``argpartition`` evicts the least recently
    used slots when a call's misses need room;
  * ``pages_fetched`` / ``fetch_hits`` / ``fetch_wall_s`` / ``fetch_calls``
    / ``staged_slots`` counters make budget pressure observable end to end
    (``PageANNIndex.fetch_stats`` -> ``EngineMetrics``): ``staged_slots``
    counts the record slots shipped to the device, so live slots over it
    is how full the shipped buffers were.

The fetcher is deliberately dumb about *placement*: which pages are
resident on device is decided once at load time
(``persist.load_pageann``); everything the device does not hold is this
module's problem, every hop.
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

from repro.obs.trace import phase

PAD = -1

# default staging-cache size (pages). Big enough to absorb the hub-page
# re-reads of a beam search over a small index, small enough that the
# host-side footprint stays a fraction of the resident region for any
# realistic page count.
DEFAULT_STAGE_PAGES = 256


def no_fetch_stats() -> dict:
    """The counters of :meth:`PageFetcher.fetch_stats` for a tier that
    fetches nothing (a fully resident index)."""
    return dict(pages_fetched=0, fetch_hits=0, fetch_wall_s=0.0,
                fetch_calls=0, staged_slots=0)


class PageFetcher:
    """Thread-safe streaming reader over a memmapped page-record file.

    ``recs`` is the (P, rows, lanes) f32 source of truth (typically an
    ``np.memmap`` of ``pages.bin``; any ndarray works). Calling the
    fetcher with an int array of page ids returns the packed records as
    f32, shape ``ids.shape + (rows, lanes)``; ids < 0 yield zero records.

    Instances are hashable by identity on purpose: the jitted streaming
    search is cached per fetcher (``core.search.stream_search``), and two
    fetchers over different files must never share a compiled closure.
    """

    def __init__(
        self,
        recs: np.ndarray,
        *,
        stage_pages: int = DEFAULT_STAGE_PAGES,
    ):
        if recs.ndim != 3:
            raise ValueError(
                f"PageFetcher needs (P, rows, lanes) records, got {recs.shape}"
            )
        if stage_pages < 1:
            raise ValueError("stage_pages must be >= 1")
        self._recs = recs
        # the map as a plain ndarray: a fancy index of it is one copy
        self._src = np.asarray(recs)
        self._stage_pages = int(stage_pages)
        self._lock = threading.Lock()
        # the staging cache: records by slot, a page's slot (-1: not
        # staged), a slot's page (-1: empty) and its last use, a stamp
        # that grows by a call's live slots each call
        slots = min(self._stage_pages, self.num_pages)
        self._stage = np.zeros((slots,) + self.record_shape, np.float32)
        self._slot_of = np.full(self.num_pages, -1, np.int64)
        self._owner = np.full(slots, -1, np.int64)
        self._last_use = np.full(slots, -1, np.int64)
        self._clock = 0
        self._pages_fetched = 0
        self._fetch_hits = 0
        self._fetch_wall_s = 0.0
        self._fetch_calls = 0
        self._staged_slots = 0
        # trailing window of per-callback wall seconds — the exposition
        # layer's fetch-latency histogram feed (bounded, like the engine's
        # latency window)
        self._wall_window: collections.deque = collections.deque(maxlen=4096)
        # optional span tracer (duck-typed, see repro.obs.trace.Tracer);
        # attached by the serving engine so per-hop host fetches land in
        # its ring buffer beside the dispatch that triggered them, stamped
        # with the tracer's own clock. A profiler trace gets the
        # ``fetch.page_fetch`` span without it.
        self.tracer = None

    @property
    def num_pages(self) -> int:
        return int(self._recs.shape[0])

    @property
    def record_shape(self) -> tuple[int, int]:
        return int(self._recs.shape[1]), int(self._recs.shape[2])

    def __call__(self, ids) -> np.ndarray:
        """Records of ``ids`` (any shape), zeros where an id is ``PAD``.

        The search loop's packed request, live ids at the front and
        ``PAD`` behind them, is filled in place: the records at the front,
        in order, and zeros behind them."""
        with phase("fetch.page_fetch", self.tracer, cat="host-fetch",
                   track="host-fetch") as span:
            t0 = time.perf_counter()
            ids = np.asarray(ids)
            flat = ids.reshape(-1).astype(np.int64)
            live = flat >= 0
            n = int(live.sum())
            out = np.empty((flat.size,) + self.record_shape, np.float32)
            if live[:n].all():
                misses = self._gather(flat[:n], out)
                out[n:] = 0.0
            else:
                recs = np.empty((n,) + self.record_shape, np.float32)
                misses = self._gather(flat[live], recs)
                out[live] = recs
                out[~live] = 0.0
            wall = time.perf_counter() - t0
            with self._lock:
                self._fetch_calls += 1
                self._staged_slots += flat.size
                self._fetch_wall_s += wall
                self._wall_window.append(wall)
            span.annotate(requested=n, misses=misses)
            return out.reshape(ids.shape + self.record_shape)

    def _gather(self, live: np.ndarray, out: np.ndarray) -> int:
        """Write the records of the page ids ``live`` to ``out[:n]``, hits
        from the staging cache and misses read off the map with one fancy
        index, and stage the misses; returns how many pages missed."""
        n = live.size
        # distinct pages, the inverse map, and each page's last slot of the
        # call (the first of the reversed ids)
        pages, last_rev, inv_rev = np.unique(
            live[::-1], return_index=True, return_inverse=True)
        if pages.size and pages[-1] >= self.num_pages:
            raise IndexError(f"page {pages[-1]} of {self.num_pages}")
        with self._lock:
            slot = self._slot_of[pages]
            hit = slot >= 0
            miss = ~hit
            m = int(miss.sum())
            # the call's distinct records, misses first: THE disk read
            distinct = np.empty((pages.size,) + self.record_shape, np.float32)
            np.take(self._src, pages[miss], axis=0, out=distinct[:m],
                    mode="clip")
            np.take(self._stage, slot[hit], axis=0, out=distinct[m:],
                    mode="clip")
            rank = np.empty(pages.size, np.intp)
            rank[miss] = np.arange(m)
            rank[hit] = np.arange(m, pages.size)
            np.take(distinct, rank[inv_rev[::-1]], axis=0, out=out[:n],
                    mode="clip")
            used = self._clock + (n - 1 - last_rev)
            self._clock += n
            self._last_use[slot[hit]] = used[hit]
            if m:
                self._stage_misses(pages[miss], used[miss], distinct[:m])
            self._pages_fetched += m
            self._fetch_hits += n - m
        return m

    def _stage_misses(self, pages: np.ndarray, used: np.ndarray,
                      recs: np.ndarray) -> None:
        """Stage a call's missed ``pages``: of the staged pages and these,
        the most recently used fill the cache (one ``argpartition``), the
        rest are evicted. Caller holds the lock."""
        slots, m = self._owner.size, pages.size
        keep = np.argpartition(np.concatenate([self._last_use, used]), m)[m:]
        new = np.sort(keep[keep >= slots]) - slots
        freed = np.ones(slots, bool)
        freed[keep[keep < slots]] = False
        freed = np.flatnonzero(freed)
        gone = self._owner[freed]
        self._slot_of[gone[gone >= 0]] = -1
        self._owner[freed] = pages[new]
        self._last_use[freed] = used[new]
        self._slot_of[pages[new]] = freed
        self._stage[freed] = recs if new.size == m else recs[new]

    # ------------------------------------------------------------- counters
    def fetch_stats(self) -> dict:
        """Cumulative counters: pages read off disk, staging-cache hits,
        wall seconds spent inside the host callback, host round trips
        (``fetch_calls``) and record slots shipped to the device
        (``staged_slots``) — plus ``wall_window``, the bounded trailing
        window of per-callback wall seconds feeding the exposition layer's
        fetch-latency histogram."""
        with self._lock:
            return dict(
                pages_fetched=self._pages_fetched,
                fetch_hits=self._fetch_hits,
                fetch_wall_s=self._fetch_wall_s,
                fetch_calls=self._fetch_calls,
                staged_slots=self._staged_slots,
                wall_window=tuple(self._wall_window),
            )

    def reset_stats(self) -> None:
        with self._lock:
            self._pages_fetched = 0
            self._fetch_hits = 0
            self._fetch_wall_s = 0.0
            self._fetch_calls = 0
            self._staged_slots = 0
            self._wall_window.clear()

    def __repr__(self) -> str:
        return (
            f"PageFetcher(pages={self.num_pages}, "
            f"stage_pages={self._stage_pages})"
        )
