"""Host-side streaming page tier: the memmap as the source of truth.

The paper's regime is disk-resident search under a memory budget; this
module is the half of it that lives on the host. A :class:`PageFetcher`
wraps the ``np.memmap`` of ``pages.bin`` and serves per-hop record
requests from the jitted search loop (reached through
a batched ``jax.pure_callback`` — one host round-trip per hop for the
whole vmapped query batch):

  * requested page ids arrive with arbitrary leading batch axes,
    ``PAD``/-1 marking slots the device does not need (resident pages,
    unselected batch lanes) — those rows come back zeroed without touching
    the file;
  * a bounded LRU **staging cache** of recently fetched records absorbs
    the re-reads a beam search naturally produces (the same hub pages are
    requested hop after hop, query after query), so a miss costs one page
    read, a re-request costs a memcpy;
  * ``pages_fetched`` / ``fetch_hits`` / ``fetch_wall_s`` counters make
    budget pressure observable end to end (``PageANNIndex.fetch_stats`` ->
    ``EngineMetrics``).

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

PAD = -1

# default staging-cache size (pages). Big enough to absorb the hub-page
# re-reads of a beam search over a small index, small enough that the
# host-side footprint stays a fraction of the resident region for any
# realistic page count.
DEFAULT_STAGE_PAGES = 256


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
        self._stage_pages = int(stage_pages)
        self._lock = threading.Lock()
        # page id -> (rows, lanes) f32 copy, most-recently-used last
        self._stage: collections.OrderedDict[int, np.ndarray] = (
            collections.OrderedDict()
        )
        self._pages_fetched = 0
        self._fetch_hits = 0
        self._fetch_wall_s = 0.0
        # trailing window of per-callback wall seconds — the exposition
        # layer's fetch-latency histogram feed (bounded, like the engine's
        # latency window)
        self._wall_window: collections.deque = collections.deque(maxlen=4096)
        # optional span tracer (duck-typed, see repro.obs.trace.Tracer);
        # attached by the serving engine so per-hop host fetches show up
        # as child spans of the dispatch that triggered them. The fetcher
        # stamps spans with the tracer's own clock.
        self.tracer = None

    @property
    def num_pages(self) -> int:
        return int(self._recs.shape[0])

    @property
    def record_shape(self) -> tuple[int, int]:
        return int(self._recs.shape[1]), int(self._recs.shape[2])

    def __call__(self, ids) -> np.ndarray:
        t0 = time.perf_counter()
        ids = np.asarray(ids)
        flat = ids.reshape(-1).astype(np.int64)
        rows, lanes = self.record_shape
        out = np.zeros((flat.size, rows, lanes), np.float32)
        with self._lock:
            fetched0 = self._pages_fetched
            for j, pid in enumerate(flat):
                if pid < 0:
                    continue
                pid = int(pid)
                rec = self._stage.get(pid)
                if rec is not None:
                    self._stage.move_to_end(pid)
                    self._fetch_hits += 1
                else:
                    # THE disk read: one page record off the memmap
                    rec = np.asarray(self._recs[pid], np.float32)
                    self._pages_fetched += 1
                    self._stage[pid] = rec
                    if len(self._stage) > self._stage_pages:
                        self._stage.popitem(last=False)     # evict LRU
                out[j] = rec
            wall = time.perf_counter() - t0
            self._fetch_wall_s += wall
            self._wall_window.append(wall)
            misses = self._pages_fetched - fetched0
        tr = self.tracer
        if tr is not None and tr.enabled:
            t1 = tr.now()
            tr.add("page_fetch", t1 - wall, t1, cat="host-fetch",
                   track="host-fetch",
                   args={"requested": int((flat >= 0).sum()),
                         "misses": misses})
        return out.reshape(ids.shape + (rows, lanes))

    # ------------------------------------------------------------- counters
    def fetch_stats(self) -> dict:
        """Cumulative counters: pages read off disk, staging-cache hits,
        and wall seconds spent inside the host callback — plus
        ``wall_window``, the bounded trailing window of per-callback wall
        seconds feeding the exposition layer's fetch-latency histogram."""
        with self._lock:
            return dict(
                pages_fetched=self._pages_fetched,
                fetch_hits=self._fetch_hits,
                fetch_wall_s=self._fetch_wall_s,
                wall_window=tuple(self._wall_window),
            )

    def reset_stats(self) -> None:
        with self._lock:
            self._pages_fetched = 0
            self._fetch_hits = 0
            self._fetch_wall_s = 0.0
            self._wall_window.clear()

    def __repr__(self) -> str:
        return (
            f"PageFetcher(pages={self.num_pages}, "
            f"stage_pages={self._stage_pages})"
        )
