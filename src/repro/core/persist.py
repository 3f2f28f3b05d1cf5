"""On-disk index persistence: the serialized page file as the product.

The paper's contribution is a co-designed *disk* layout (Sec 4.2/4.3), so
the saved artifact mirrors it literally:

  <dir>/manifest.json   versioned JSON: kind, config, geometry, build stats
  <dir>/pages.bin       the packed page records (``PageStore.recs``) as a
                        raw page-aligned f32 binary — each page record is
                        ``rows * 128 * 4`` bytes with ``rows`` a multiple
                        of 8, i.e. a whole number of 4 KB disk pages —
                        opened with ``np.memmap`` on load
  <dir>/arrays.npz      numpy sidecars: memory tier, LSH router, id maps,
                        per-page counts and neighbor ids

``save_index`` / ``load_index`` round-trip a :class:`PageANNIndex` to
bit-identical ``SearchResult``s; ``load_index`` dispatches on the
manifest's ``kind`` so any :class:`repro.core.protocol.VectorIndex`
implementation (PageANN, the DiskANN/Starling baselines, or a mutable
index) reloads through one entry point. One level up,
``save_database`` / ``load_database`` persist a whole multi-collection
service as ``db.json`` (collection name -> subdirectory, versioned the
same way) over ordinary per-collection artifacts — the on-disk form of
:class:`repro.serve.service.VectorService`. A mutable index
(:class:`repro.core.delta.MutableIndex`) persists as kind="mutable": the
frozen base as a nested artifact under ``base/`` plus a ``delta.npz``
sidecar (inserted vectors + liveness + tombstones + external id map) and a
manifest ``generation`` counter; compaction replaces the whole directory
atomically (``swap_mutable``: sibling tmp dir + two renames). Unreadable
artifacts — truncated ``pages.bin``, garbled manifests, versions ahead of
this build — raise :class:`IndexFormatError` naming what was found vs
supported. Host-side views that the search path never touches
(``PageStore.vecs`` / ``PageStore.nbr_codes``) are *not* persisted — they
are unpacked from the page file itself (``layout.unpack_member_vectors`` /
``unpack_neighbor_codes``), keeping the artifact a single copy of the disk
tier. (MEM_ALL is the exception for codes: its records drop the code rows,
so the codes ride the npz.)
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np

from repro.core import layout as layout_mod
from repro.core import search as search_mod
from repro.core.config import MemoryMode, PageANNConfig
from repro.core.lsh import LSHIndex

FORMAT = "repro.vector_index"
VERSION = 1

MANIFEST = "manifest.json"
PAGES_BIN = "pages.bin"
ARRAYS_NPZ = "arrays.npz"
META_NPZ = "meta.npz"
DELTA_NPZ = "delta.npz"
BASE_SUBDIR = "base"

# ---- database layout (a directory of named collections, see save_database)
DB_FORMAT = "repro.vector_database"
DB_VERSION = 1
DB_MANIFEST = "db.json"
DB_COLLECTIONS_SUBDIR = "collections"

# collection names double as artifact subdirectory names, so they are
# restricted to a filesystem- and manifest-safe alphabet up front — a
# rejected create_collection beats a corrupted db.json or a path traversal
_NAME_ALLOWED = (
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789._-"
)


def check_collection_name(name: str) -> str:
    """Validate a collection name (also used as its on-disk subdirectory):
    1-64 chars of [A-Za-z0-9._-], not starting with a dot or dash."""
    if (
        not isinstance(name, str)
        or not 0 < len(name) <= 64
        or name[0] in ".-"
        or any(c not in _NAME_ALLOWED for c in name)
    ):
        raise ValueError(
            f"invalid collection name {name!r}: need 1-64 chars of "
            "[A-Za-z0-9._-] not starting with '.' or '-'"
        )
    return name


class IndexFormatError(ValueError):
    """A saved index artifact this library cannot read: corrupted or
    truncated files, a missing/garbled manifest, or a format version ahead
    of what this build supports. Subclasses ``ValueError`` so older
    call sites catching that keep working."""


def is_index_dir(directory: str) -> bool:
    return os.path.isfile(os.path.join(directory, MANIFEST))


def write_manifest(directory: str, doc: dict) -> None:
    doc = dict(doc, format=FORMAT, version=VERSION)
    with open(os.path.join(directory, MANIFEST), "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)


def read_manifest(directory: str) -> dict:
    path = os.path.join(directory, MANIFEST)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no index manifest at {path}")
    try:
        with open(path) as f:
            doc = json.load(f)
    except json.JSONDecodeError as e:
        raise IndexFormatError(f"{path}: manifest is not valid JSON: {e}")
    if doc.get("format") != FORMAT:
        raise IndexFormatError(f"{path}: not a {FORMAT} manifest")
    found = doc.get("version")
    if found != VERSION:
        ahead = isinstance(found, int) and found > VERSION
        hint = (
            "; artifact was written by a newer library — upgrade to read it"
            if ahead else ""
        )
        raise IndexFormatError(
            f"{path}: found format version {found}, this build supports "
            f"version {VERSION}{hint}"
        )
    return doc


def _check_pages_bin(directory: str, doc: dict) -> str:
    """The page file must exist and hold exactly the manifest's geometry —
    a truncated copy must fail loudly here, not as a numpy reshape error
    deep in ``np.memmap``."""
    path = os.path.join(directory, PAGES_BIN)
    if not os.path.isfile(path):
        raise IndexFormatError(f"{path}: missing page file")
    want = doc["pages"] * doc["record_rows"] * doc["record_lanes"] * 4
    got = os.path.getsize(path)
    if got != want:
        raise IndexFormatError(
            f"{path}: corrupted or truncated page file — {got} bytes on "
            f"disk, manifest geometry needs {want} "
            f"({doc['pages']} pages x {doc['page_record_bytes']} B)"
        )
    return path


def _schema_to_json(index) -> dict | None:
    """The manifest ``schema`` section: field declaration + tag
    vocabulary. ``None`` when the index carries no metadata."""
    schema = getattr(index, "schema", None)
    if schema is None:
        return None
    doc = schema.to_json()
    doc["vocab"] = {f: list(vs) for f, vs in index.vocab.items()}
    return doc


def _load_meta(directory: str, doc: dict, store):
    """Reconstruct (schema, vocab, meta, meta_host) from the manifest
    ``schema`` section + ``meta.npz`` sidecar. The two must agree — a
    sidecar swapped in from another collection (or a manifest edited by
    hand) fails here as :class:`IndexFormatError`, not as a shape error
    deep inside the first filtered search."""
    from repro.core import filter as filter_mod
    from repro.core.filter import MetaArrays, MetadataSchema

    schema_doc = doc.get("schema")
    path = os.path.join(directory, META_NPZ)
    if schema_doc is None:
        if os.path.isfile(path):
            raise IndexFormatError(
                f"{path}: metadata sidecar present but the manifest has "
                "no schema section"
            )
        return None, {}, None, None
    if not os.path.isfile(path):
        raise IndexFormatError(
            f"{path}: manifest declares a metadata schema but the "
            "metadata sidecar is missing"
        )
    try:
        schema = MetadataSchema.from_json(schema_doc)
        vocab = {
            f: tuple(vs) for f, vs in schema_doc.get("vocab", {}).items()
        }
    except (TypeError, ValueError, AttributeError) as e:
        raise IndexFormatError(
            f"{directory}: garbled manifest schema section: {e}"
        )
    unknown = sorted(set(vocab) - set(schema.tags) - set(schema.bag_names))
    if unknown:
        raise IndexFormatError(
            f"{directory}: manifest vocab names fields not in the "
            f"schema: {unknown}"
        )
    with np.load(path) as z:
        if not {"tags", "nums"} <= set(z.files):
            raise IndexFormatError(
                f"{path}: metadata sidecar is missing arrays "
                f"(found {sorted(z.files)}, need ['nums', 'tags'])"
            )
        slot_tags = np.asarray(z["tags"], np.int32)
        slot_nums = np.asarray(z["nums"], np.float32)
    rows = int(np.asarray(store.new_to_old).shape[0])  # pages * capacity
    want_tags = (rows, schema.tag_width)
    want_nums = (rows, len(schema.numerics))
    if slot_tags.shape != want_tags or slot_nums.shape != want_nums:
        raise IndexFormatError(
            f"{path}: metadata shapes {slot_tags.shape}/{slot_nums.shape} "
            f"disagree with the manifest schema — expected "
            f"{want_tags}/{want_nums}"
        )
    # every stored code must name a vocabulary value: the posting lists
    # built from these columns index by code
    spans = [(i, 1, f) for i, f in enumerate(schema.tags)] + [
        (c0, w, f) for f, (c0, w) in schema.bag_columns().items()]
    for c0, w, f in spans:
        if slot_tags[:, c0:c0 + w].max(initial=-1) >= len(vocab.get(f, ())):
            raise IndexFormatError(
                f"{path}: field {f!r} holds codes outside its vocabulary "
                f"of {len(vocab.get(f, ()))} values"
            )
    host_tags, host_nums = layout_mod.unreassign_metadata(
        slot_tags, slot_nums, store
    )
    return (
        schema,
        vocab,
        MetaArrays(tags=jnp.asarray(slot_tags), nums=jnp.asarray(slot_nums)),
        MetaArrays(tags=host_tags, nums=host_nums),
    )


def config_to_json(cfg: PageANNConfig) -> dict:
    doc = dataclasses.asdict(cfg)
    doc["memory_mode"] = cfg.memory_mode.value
    return doc


def config_from_json(doc: dict) -> PageANNConfig:
    doc = dict(doc)
    doc["memory_mode"] = MemoryMode(doc["memory_mode"])
    return PageANNConfig(**doc)


# ------------------------------------------------------------------ PageANN
def save_pageann(index, directory: str) -> None:
    """Write a built :class:`PageANNIndex` under ``directory``."""
    os.makedirs(directory, exist_ok=True)
    store, tier, lsh = index.store, index.tier, index.lsh

    # a streamed store's device ``recs`` holds only the resident subset;
    # the host memmap is the full page file and the source of truth
    recs_full = store.recs_host if store.recs_host is not None else store.recs
    recs = np.ascontiguousarray(np.asarray(recs_full, np.float32))
    recs.tofile(os.path.join(directory, PAGES_BIN))

    sidecars = {}
    if index.cfg.memory_mode == MemoryMode.MEM_ALL:
        # MEM_ALL records carry no code rows, so the host-side codes view
        # is not recoverable from pages.bin — persist it explicitly
        sidecars["nbr_codes"] = np.asarray(store.nbr_codes)
    page_order = getattr(index, "page_order", None)
    if page_order is not None:
        # full hotness ordering (warm_cache access counts, hottest first):
        # the residency policy a budgeted load pins pages by
        sidecars["page_order"] = np.asarray(page_order, np.int32)
    np.savez(
        os.path.join(directory, ARRAYS_NPZ),
        **sidecars,
        member_count=np.asarray(store.member_count),
        nbr_ids=np.asarray(store.nbr_ids),
        nbr_count=np.asarray(store.nbr_count),
        new_to_old=np.asarray(store.new_to_old),
        old_to_new=np.asarray(store.old_to_new),
        mem_codes=np.asarray(tier.mem_codes),
        mem_mask=np.asarray(tier.mem_mask),
        mem_codebooks=np.asarray(tier.mem_codebooks),
        disk_codebooks=np.asarray(tier.disk_codebooks),
        cached_pages=np.asarray(tier.cached_pages),
        lsh_planes=np.asarray(lsh.planes),
        lsh_sample_ids=np.asarray(lsh.sample_ids),
        lsh_sample_codes=np.asarray(lsh.sample_codes),
        lsh_sample_pq=np.asarray(lsh.sample_pq),
        # the hyperplanes' centre, where the build moved it off the origin
        # (core.lsh); an artifact without it hashes about the origin
        **({} if lsh.center is None
           else {"lsh_center": np.asarray(lsh.center, np.float32)}),
    )
    if getattr(index, "schema", None) is not None:
        # page-slot-aligned metadata columns ride their own sidecar: the
        # same row order as pages.bin, so a page's metadata is one
        # contiguous slice at the page's slot offsets
        np.savez(
            os.path.join(directory, META_NPZ),
            tags=np.asarray(index.meta.tags, np.int32),
            nums=np.asarray(index.meta.nums, np.float32),
        )

    pages, rows, lanes = recs.shape
    write_manifest(
        directory,
        dict(
            kind="pageann",
            config=config_to_json(index.cfg),
            pages=pages,
            record_rows=rows,
            record_lanes=lanes,
            page_record_bytes=rows * lanes * 4,
            capacity=store.capacity,
            dim=store.dim,
            stats=dataclasses.asdict(index.stats),
            # warm-cache persistence: the hot page ids ride the manifest so
            # a loaded server starts with the builder's warmed cache
            hot_pages=np.asarray(tier.cached_pages).tolist(),
            # residency metadata: how THIS index was loaded/built. The
            # budget round-trips so a re-saved streamed index records its
            # provenance; a fresh load still chooses its own budget.
            residency=dict(
                memory_budget=(
                    index.memory_budget.to_json()
                    if getattr(index, "memory_budget", None) is not None
                    else None
                ),
                resident_pages=store.resident_pages,
                total_pages=pages,
            ),
            # autotuned operating points (index.autotune): measured
            # {params, recall, qps, ...} entries plus which one serving
            # should resolve as the default SearchParams
            tuned=_tuned_to_json(index),
            # metadata declaration + tag vocabulary (None: no metadata);
            # the encoded columns themselves live in meta.npz
            schema=_schema_to_json(index),
        ),
    )


def _tuned_to_json(index) -> dict:
    points = []
    for m in getattr(index, "tuned", []) or []:
        doc = {
            key: val for key, val in m.items() if key != "params"
        }
        doc["params"] = m["params"].to_json()
        points.append(doc)
    default = getattr(index, "tuned_default", None)
    return dict(
        default=default.to_json() if default is not None else None,
        points=points,
    )


def _tuned_from_json(doc: dict | None) -> tuple[list, "SearchParams | None"]:
    from repro.core.config import SearchParams

    if not doc:            # pre-adaptive artifacts carry no tuned section
        return [], None
    points = []
    for entry in doc.get("points", []):
        m = dict(entry)
        m["params"] = SearchParams.from_json(m["params"])
        points.append(m)
    default = doc.get("default")
    return points, (
        SearchParams.from_json(default) if default is not None else None
    )


def _page_order_of(doc: dict, arrays: dict) -> np.ndarray:
    """Full residency priority, hottest page first: the persisted
    ``page_order`` sidecar (warm_cache access counts) when the artifact
    carries one, else the manifest's hot pages followed by the rest in id
    order — a valid (if unmeasured) policy for pre-streaming artifacts."""
    pages = int(doc["pages"])
    if "page_order" in arrays:
        return np.asarray(arrays["page_order"], np.int32)
    hot = np.asarray(doc.get("hot_pages", []), np.int32)
    rest = np.setdiff1d(np.arange(pages, dtype=np.int32), hot)
    return np.concatenate([hot, rest])[:pages]


def load_pageann(directory: str, *, memory_budget=None):
    """Reload a saved index; search results are bit-identical to the
    in-memory index that was saved.

    ``memory_budget`` (a :class:`repro.core.config.MemoryBudget`, or None)
    caps the device-resident page-record region: the hottest pages that fit
    are pinned on device, every other page stays in the ``pages.bin``
    memmap and is fetched per hop through a :class:`core.stream.PageFetcher`
    host callback. Results stay bit-identical to a fully resident load —
    only where the record bytes are gathered from changes. ``None`` (the
    default) is always fully resident, today's behavior."""
    from repro.core import stream as stream_mod
    from repro.core.config import MemoryBudget
    from repro.core.index import BuildStats, PageANNIndex

    doc = read_manifest(directory)
    if doc["kind"] != "pageann":
        raise ValueError(f"{directory}: kind={doc['kind']!r}, not a PageANN index")
    cfg = config_from_json(doc["config"])

    # the literal paper disk layout: raw page-aligned records via memmap
    pages_path = _check_pages_bin(directory, doc)
    recs_mm = np.memmap(
        pages_path,
        dtype=np.float32,
        mode="r",
        shape=(doc["pages"], doc["record_rows"], doc["record_lanes"]),
    )
    with np.load(os.path.join(directory, ARRAYS_NPZ)) as z:
        arrays = {name: z[name] for name in z.files}

    if "nbr_codes" in arrays:                     # MEM_ALL sidecar
        nbr_codes = arrays["nbr_codes"]
    else:                                         # recover from the records
        nbr_codes = layout_mod.unpack_neighbor_codes(
            recs_mm, doc["capacity"], doc["dim"],
            rp=arrays["nbr_ids"].shape[1], m=cfg.pq_subspaces,
        )

    page_order = _page_order_of(doc, arrays)
    num_pages = int(doc["pages"])
    fetcher = None
    if memory_budget is not None:
        memory_budget = MemoryBudget.parse(memory_budget)
        n_res = memory_budget.resolve_pages(
            num_pages, int(doc["page_record_bytes"])
        )
    else:
        n_res = num_pages
    if n_res >= num_pages:
        # everything fits: plain fully resident load (identity residency,
        # no fetcher) — shares compiled executables with unbudgeted loads
        resident_map = None
        recs_dev = jnp.asarray(recs_mm)
        recs_host = None
    else:
        # pin the hottest pages that fit; sort the kept ids so the device
        # region preserves relative page order (gather locality)
        resident_ids = np.sort(page_order[:n_res])
        rmap = np.full(num_pages, stream_mod.PAD, np.int32)
        rmap[resident_ids] = np.arange(n_res, dtype=np.int32)
        resident_map = jnp.asarray(rmap)
        recs_dev = jnp.asarray(np.asarray(recs_mm[resident_ids], np.float32))
        recs_host = recs_mm
        fetcher = stream_mod.PageFetcher(recs_mm)

    store = layout_mod.PageStore(
        vecs=layout_mod.unpack_member_vectors(
            recs_mm, doc["capacity"], doc["dim"]
        ),
        member_count=jnp.asarray(arrays["member_count"]),
        nbr_ids=jnp.asarray(arrays["nbr_ids"]),
        nbr_codes=nbr_codes,
        nbr_count=jnp.asarray(arrays["nbr_count"]),
        recs=recs_dev,
        capacity=doc["capacity"],
        dim=doc["dim"],
        new_to_old=arrays["new_to_old"],
        old_to_new=arrays["old_to_new"],
        resident_map=resident_map,
        recs_host=recs_host,
    )
    # warm-cache persistence: the manifest's hot page ids pre-populate the
    # cache so a restarted server serves the first query warm (the npz copy
    # is the fallback for artifacts saved before hot_pages existed)
    hot = np.asarray(
        doc.get("hot_pages", arrays["cached_pages"]), np.int32
    )
    tier = layout_mod.MemoryTier(
        mem_codes=jnp.asarray(arrays["mem_codes"]),
        mem_mask=jnp.asarray(arrays["mem_mask"]),
        mem_codebooks=jnp.asarray(arrays["mem_codebooks"]),
        disk_codebooks=jnp.asarray(arrays["disk_codebooks"]),
        cached_pages=jnp.asarray(np.sort(hot)),
    )
    lsh = LSHIndex(
        planes=jnp.asarray(arrays["lsh_planes"]),
        sample_ids=jnp.asarray(arrays["lsh_sample_ids"]),
        sample_codes=jnp.asarray(arrays["lsh_sample_codes"]),
        sample_pq=jnp.asarray(arrays["lsh_sample_pq"]),
        center=(jnp.asarray(arrays["lsh_center"]) if "lsh_center" in arrays
                else None),
    )
    # stats.disk_bytes reports the persisted artifact as it sits on disk,
    # not a recomputation from device arrays (see BuildStats docstring)
    stats = BuildStats(**doc["stats"])
    stats.disk_bytes = os.path.getsize(pages_path)
    stats.resident_pages = store.resident_pages
    stats.resident_bytes = store.resident_bytes
    tuned, tuned_default = _tuned_from_json(doc.get("tuned"))
    schema, vocab, meta, meta_host = _load_meta(directory, doc, store)
    return PageANNIndex(
        cfg=cfg,
        store=store,
        tier=tier,
        lsh=lsh,
        data=search_mod.make_search_data(store, tier, lsh),
        stats=stats,
        fetcher=fetcher,
        page_order=page_order,
        memory_budget=memory_budget,
        tuned=tuned,
        tuned_default=tuned_default,
        schema=schema,
        vocab=vocab,
        meta=meta,
        meta_host=meta_host,
    )


# ----------------------------------------------------------------- mutable
def save_mutable(state, directory: str) -> None:
    """Write a :class:`repro.core.delta.MutableIndex` state under
    ``directory``: the frozen base as a full nested artifact plus a
    ``delta.npz`` sidecar (inserted vectors, liveness, tombstones, external
    id map) — a restarted server reloads the dirty index losslessly."""
    os.makedirs(directory, exist_ok=True)
    state.base.save(os.path.join(directory, BASE_SUBDIR))
    dv = state.delta
    c = dv.count
    extra = {}
    if getattr(state.base, "schema", None) is not None:
        extra = dict(
            delta_tags=np.asarray(dv.tags[:c], np.int32),
            delta_nums=np.asarray(dv.nums[:c], np.float32),
        )
    np.savez(
        os.path.join(directory, DELTA_NPZ),
        delta_vecs=np.asarray(dv.vecs[:c], np.float32),
        delta_ids=np.asarray(dv.ids[:c], np.int64),
        delta_live=np.asarray(dv.live[:c], bool),
        tombstones=np.asarray(state.tombstones, np.int64),
        base_ids=np.asarray(state.base_ids, np.int64),
        **extra,
    )
    write_manifest(
        directory,
        dict(
            kind="mutable",
            base_kind=read_manifest(os.path.join(directory, BASE_SUBDIR))[
                "kind"
            ],
            dim=state.base.dim,
            generation=state.generation,
            base_rows=int(state.base_ids.size),
            delta_rows=int(c),
            delta_live=int(dv.n_live),
            tombstones=int(state.tombstones.size),
            # the UNIFIED vocabulary (base + values seen only in delta
            # inserts) — delta tag codes are positions in these tuples
            vocab=(
                {f: list(vs) for f, vs in state.vocab.items()}
                if state.vocab is not None else None
            ),
        ),
    )


def swap_mutable(state, directory: str) -> None:
    """Replace the artifact at ``directory`` with ``state`` (the
    compaction swap): write a sibling tmp dir, then two renames. Both
    sides of the swap are always intact on disk — no reader ever sees a
    half-written directory, and in-process readers holding memmaps of the
    old files keep valid fds. The canonical path is briefly absent between
    the two renames: a crash in that window leaves the previous artifact
    complete under ``<dir>.old.<gen>`` (and the new one under
    ``<dir>.tmp.<gen>``); the next swap — or a manual rename — recovers
    it. Stale ``.tmp``/``.old`` siblings from any crashed earlier swap are
    swept first."""
    import glob
    import shutil

    clean = directory.rstrip(os.sep)
    for leftover in glob.glob(f"{glob.escape(clean)}.tmp.*") + glob.glob(
        f"{glob.escape(clean)}.old.*"
    ):
        if os.path.isdir(leftover):
            shutil.rmtree(leftover)
    tmp = f"{clean}.tmp.{state.generation}"
    old = f"{clean}.old.{state.generation}"
    save_mutable(state, tmp)
    os.rename(clean, old)
    os.rename(tmp, clean)
    shutil.rmtree(old)


def load_mutable(directory: str, *, memory_budget=None):
    """Reload a saved mutable index (base + delta sidecar); searches on
    the loaded index are bit-identical to the saved dirty state.
    ``memory_budget`` applies to the frozen base tier (the delta tier is
    in-memory by construction)."""
    from repro.core.delta import MutableIndex

    doc = read_manifest(directory)
    if doc["kind"] != "mutable":
        raise ValueError(
            f"{directory}: kind={doc['kind']!r}, not a mutable index"
        )
    base = load_index(
        os.path.join(directory, BASE_SUBDIR), memory_budget=memory_budget
    )
    npz_path = os.path.join(directory, DELTA_NPZ)
    if not os.path.isfile(npz_path):
        raise IndexFormatError(f"{npz_path}: missing delta sidecar")
    with np.load(npz_path) as z:
        arrays = {name: z[name] for name in z.files}

    index = MutableIndex(base, base_ids=arrays["base_ids"])
    live = arrays["delta_live"]
    if live.size:
        # restore the append log verbatim (the log may hold dead rows for
        # superseded/deleted ids): slot numbering — and thus scan output —
        # is bit-identical to the saved index
        c = int(live.size)
        tier = index._delta
        tier._grow(c)
        tier._vecs[:c] = arrays["delta_vecs"]
        tier._ids[:c] = arrays["delta_ids"]
        tier._live[:c] = live
        if "delta_tags" in arrays:
            tier._tags[:c] = arrays["delta_tags"]
            tier._nums[:c] = arrays["delta_nums"]
        tier._count = c
        tier._slot_of = {
            int(arrays["delta_ids"][i]): i for i in range(c) if live[i]
        }
        tier._view = None
    vocab_doc = doc.get("vocab")
    if vocab_doc is not None:
        # the persisted UNIFIED vocabulary supersedes the base's copy the
        # constructor installed — delta tag codes index into this one
        index._vocab = {f: tuple(vs) for f, vs in vocab_doc.items()}
    index._state = index._state._replace(
        tombstones=np.asarray(arrays["tombstones"], np.int64),
        delta=index._delta.snapshot(),
        generation=int(doc.get("generation", 0)),
        vocab=dict(index._vocab) if vocab_doc is not None else (
            index._state.vocab
        ),
    )
    index._next_id = int(
        max(
            arrays["base_ids"].max(initial=-1),
            arrays["delta_ids"].max(initial=-1),
        )
        + 1
    )
    index._directory = directory
    return index


# ----------------------------------------------------------------- database
def is_database_dir(directory: str) -> bool:
    return os.path.isfile(os.path.join(directory, DB_MANIFEST))


def read_db_manifest(directory: str) -> dict:
    """Read and validate ``db.json`` (versioned exactly like index
    manifests: wrong format / garbled JSON / version-ahead all raise
    :class:`IndexFormatError` naming found vs supported)."""
    path = os.path.join(directory, DB_MANIFEST)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no database manifest at {path}")
    try:
        with open(path) as f:
            doc = json.load(f)
    except json.JSONDecodeError as e:
        raise IndexFormatError(f"{path}: database manifest is not valid JSON: {e}")
    if doc.get("format") != DB_FORMAT:
        raise IndexFormatError(f"{path}: not a {DB_FORMAT} manifest")
    found = doc.get("version")
    if found != DB_VERSION:
        ahead = isinstance(found, int) and found > DB_VERSION
        hint = (
            "; database was written by a newer library — upgrade to read it"
            if ahead else ""
        )
        raise IndexFormatError(
            f"{path}: found database version {found}, this build supports "
            f"version {DB_VERSION}{hint}"
        )
    if not isinstance(doc.get("collections"), dict):
        raise IndexFormatError(f"{path}: manifest has no collections table")
    return doc


def _collection_subdir(name: str) -> str:
    # stored with a literal "/" so db.json is platform-independent
    return f"{DB_COLLECTIONS_SUBDIR}/{name}"


def save_database(collections, directory: str) -> None:
    """Persist a whole multi-collection service under one directory:

      <dir>/db.json                versioned JSON: collection name -> subdir
      <dir>/collections/<name>/    one full per-collection index artifact
                                   (whatever kind each index persists as)

    ``collections`` maps name -> any ``VectorIndex`` with ``save``.  For a
    FRESH directory the manifest is written last (atomically: tmp +
    rename), so a crash mid-save leaves a directory that ``load_database``
    refuses (no db.json) rather than a silently partial database.
    Re-saving over an existing database overwrites the per-collection
    artifacts in place under the still-valid old manifest — for an atomic
    replacement of a live database, save to a fresh sibling directory and
    rename (the ``swap_mutable`` pattern).  Round-trips through
    :func:`load_database` / ``repro.serve.VectorService.load``.
    """
    for name in collections:
        check_collection_name(name)
    os.makedirs(directory, exist_ok=True)
    table = {}
    for name, index in sorted(collections.items()):
        sub = _collection_subdir(name)
        index.save(os.path.join(directory, DB_COLLECTIONS_SUBDIR, name))
        table[name] = sub
    doc = dict(format=DB_FORMAT, version=DB_VERSION, collections=table)
    path = os.path.join(directory, DB_MANIFEST)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def load_database(directory: str, *, memory_budget=None) -> dict:
    """Reload every collection of a saved database: name -> loaded
    ``VectorIndex`` (each dispatched through :func:`load_index` on its
    manifest kind). Searches on the loaded indexes are bit-identical to
    the saved ones. ``memory_budget`` applies PER COLLECTION (each
    collection's page tier is capped independently).

    Artifact paths are derived from the VALIDATED collection names, never
    from manifest values: a tampered ``db.json`` mapping a name outside
    ``collections/`` is rejected, not followed."""
    doc = read_db_manifest(directory)
    out = {}
    for name, sub in sorted(doc["collections"].items()):
        check_collection_name(name)
        want = _collection_subdir(name)
        if sub != want:
            raise IndexFormatError(
                f"{directory}: collection {name!r} maps to unexpected "
                f"path {sub!r} (expected {want!r})"
            )
        out[name] = load_index(
            os.path.join(directory, DB_COLLECTIONS_SUBDIR, name),
            memory_budget=memory_budget,
        )
    return out


# ----------------------------------------------------------------- dispatch
def load_index(directory: str, *, memory_budget=None):
    """Load whichever :class:`VectorIndex` implementation saved ``directory``.

    ``memory_budget`` (``MemoryBudget`` | bytes | fraction | spec string |
    None) caps the device-resident page region of indexes with a page tier
    (PageANN, and the base tier of a mutable index); ``None`` keeps
    everything resident. Baseline kinds have no page tier and reject a
    budget loudly rather than silently ignoring it."""
    from repro.core import baselines as bl

    kind = read_manifest(directory)["kind"]
    if kind == "pageann":
        return load_pageann(directory, memory_budget=memory_budget)
    if kind == "mutable":
        return load_mutable(directory, memory_budget=memory_budget)
    if kind == "sharded":
        # lazy: repro.dist sits above core and imports this module
        from repro.dist.sharded import ShardedPageStore

        return ShardedPageStore.load(directory, memory_budget=memory_budget)
    if kind in bl.BASELINE_KINDS:
        if memory_budget is not None:
            raise ValueError(
                f"{directory}: kind={kind!r} baseline indexes are fully "
                "in-memory; memory_budget is not supported"
            )
        return bl.load_baseline(directory)
    raise ValueError(f"{directory}: unknown index kind {kind!r}")
