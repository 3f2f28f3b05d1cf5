"""The unified index lifecycle contract: build → save → load → search.

Every searchable index in this repo — :class:`repro.core.index.PageANNIndex`
and the DiskANN/Starling baselines in :mod:`repro.core.baselines` — speaks
the same small surface, so tests compare all systems through one code
path and the serving layer is implementation-agnostic: the
collection-agnostic :class:`repro.serve.BatchingEngine` batches requests
per ``(collection, k-bin, params)`` group, and the database-level
:class:`repro.serve.VectorService` registers any number of named
``VectorIndex`` collections on one shared core (whole databases persist
via ``repro.core.persist.save_database`` — a versioned ``db.json`` over
per-collection artifacts):

  * ``search(queries, k=None, params=None) -> SearchResult`` — runtime
    knobs arrive per call as a :class:`repro.core.config.SearchParams`
    (``k`` overrides ``params.k`` when given); results carry ORIGINAL
    vector ids and the paper's I/O accounting.
  * ``save(directory)`` — persist the index artifact to disk.
  * ``load(directory)`` (classmethod) — reload it; searches on the loaded
    index are bit-identical to the saved one. Implementations with a page
    tier additionally accept ``load(directory, memory_budget=...)`` (a
    :class:`repro.core.config.MemoryBudget`): the hottest pages that fit
    are pinned on device and the rest stream from the ``pages.bin`` memmap
    per hop — same results, bounded device footprint.
  * ``stats`` — build/footprint statistics object. Disk footprint numbers
    describe the artifact as persisted: an index loaded via memmap reports
    the actual on-disk byte size of its page file
    (``BuildStats.disk_bytes``), not a recomputation from device arrays.
    The resident/streamed split rides the same object —
    ``resident_pages`` / ``resident_bytes`` vs ``disk_bytes``.
  * ``dim`` — vector dimensionality accepted by ``search``.

:class:`MutableVectorIndex` extends the contract with writes —
``insert`` / ``delete`` / ``compact`` — implemented by
:class:`repro.core.delta.MutableIndex` (in-memory delta tier + tombstones
over a frozen base, folded back into the disk artifact on compaction).

``repro.core.persist.load_index`` reopens a saved directory as whichever
implementation wrote it.
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import numpy as np

from repro.core.config import SearchParams
from repro.core.search import SearchResult


@runtime_checkable
class VectorIndex(Protocol):
    @property
    def dim(self) -> int: ...

    @property
    def default_params(self) -> SearchParams: ...

    @property
    def stats(self): ...

    def search(
        self,
        queries: np.ndarray,
        k: int | None = None,
        params: SearchParams | None = None,
    ) -> SearchResult: ...

    def save(self, directory: str) -> None: ...

    @classmethod
    def load(cls, directory: str) -> "VectorIndex": ...


@runtime_checkable
class MutableVectorIndex(VectorIndex, Protocol):
    """A ``VectorIndex`` that accepts writes between searches.

    ``insert`` returns the external ids assigned to the new vectors (caller
    ids echoed back, or freshly allocated when omitted); ``delete`` returns
    how many ids were live; ``compact`` folds pending writes into a fresh
    base artifact and returns whether anything was folded. Writes must
    interleave safely with concurrent ``search`` calls.
    """

    def insert(
        self, vectors: np.ndarray, ids: np.ndarray | None = None
    ) -> np.ndarray: ...

    def delete(self, ids: np.ndarray) -> int: ...

    def compact(self) -> bool: ...
