"""Metadata schemas and filter expressions for filtered search.

Production vector queries are rarely bare top-k: they carry predicates
("this user's docs", "created after T", "photos tagged beach AND
sunset"). This module gives each collection a declared
:class:`MetadataSchema` (tag fields: one value of a string vocabulary per
vector; bag fields: a set of up to ``width`` such values per vector;
numeric fields: float scalars), stores the per-vector metadata as packed
**page-slot-aligned columns** (the same ``new_to_old`` scatter the page
records use, so a page's metadata rows sit at the page's slot offsets),
and compiles a frozen/hashable :class:`FilterExpr` into a
:class:`CompiledFilter`: a static :class:`FilterShape` (which clauses
over which columns, how many code slots each) and per-query **values**
(codes, numeric bounds). The search carries the values as arrays beside
the queries, so one compiled program serves every predicate of a shape.

Layers:

  * ``MetadataSchema`` — declares the fields; validated like
    ``AdaptiveParams`` (every violation in one ``ValueError``);
    JSON round-trips through the index manifest.
  * ``Tag("f") == v`` / ``.isin(...)``, ``Bag("f").contains_all(...)``
    and ``Num("f").between/ge/le`` build ``FilterExpr`` clauses; ``&``
    ANDs expressions. Expressions are frozen and hashable.
  * ``compile_filter(expr, schema, vocab)`` resolves field names to
    column indices and tag values to integer codes. Unknown *fields*
    are errors (reported together); unknown tag *values* simply match
    nothing — a predicate over a value no vector carries is a valid
    query with an empty answer, not a schema violation.
  * ``filter_mask`` (jnp, per query under vmap) / ``filter_mask_np``
    (numpy) evaluate a compiled filter over metadata columns. The numpy
    twin is the brute-force oracle.
  * ``build_postings`` / ``match_ids`` — per tag value, the ascending
    original ids of the vectors that carry it (CSR per field), and the
    exact match set of a compiled filter from them: the match count the
    query planner (``core.plan``) routes by, and the rows the exact
    path scans.

Encoding invariants (shared with the delta tier and persistence):

  * stored tag codes are ``>= 0``; **missing/pad = -1** (matches no
    clause). A bag field of width ``w`` is ``w`` code columns, its codes
    distinct and padded with -1;
  * numeric missing/pad = ``NaN`` (range comparisons are False);
  * a vocabulary maps each tag or bag field to a tuple of values; codes
    are positions in that tuple. ``MutableIndex`` extends vocabularies
    append-only, so codes stay stable across inserts until compaction.
  * a clause's code slots are padded with ``PAD_CODE`` (no value in an
    ``isin`` clause, no requirement in a bag clause); a bag clause value
    the vocabulary lacks compiles to ``NO_CODE``, which no row holds.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax.numpy as jnp
import numpy as np

_MISSING_TAG = -1  # tag code for "no value": valid codes are >= 0
PAD_CODE = -2      # an unused code slot of a clause: neutral in OR and AND
NO_CODE = -3       # a bag-clause value outside the vocabulary: held by none


class MetaArrays(NamedTuple):
    """Packed metadata columns, page-slot-aligned like ``PageStore.vecs``.

    ``tags``: (rows, tag_width) int32 codes (missing/pad = -1): one column
    per tag field, then ``width`` columns per bag field.
    ``nums``: (rows, n_num_fields) float32 (missing/pad = NaN).
    Either axis-1 may be 0 when the schema has no fields of that kind.
    """

    tags: Any
    nums: Any


# --------------------------------------------------------------------- schema
@dataclasses.dataclass(frozen=True)
class MetadataSchema:
    """Per-collection metadata declaration: which fields exist and their
    kinds. ``tags`` are categorical string fields (vocabulary-encoded);
    ``bags`` are ``(field, width)`` pairs (or a ``{field: width}`` dict),
    each a set of up to ``width`` values of a vocabulary per vector;
    ``numerics`` are scalar float fields (range-filterable)."""

    tags: tuple[str, ...] = ()
    numerics: tuple[str, ...] = ()
    bags: tuple[tuple[str, int], ...] = ()

    def __post_init__(self):
        bags = self.bags.items() if isinstance(self.bags, dict) else self.bags
        object.__setattr__(self, "tags", tuple(self.tags))
        object.__setattr__(self, "numerics", tuple(self.numerics))
        object.__setattr__(self, "bags", tuple((n, w) for n, w in bags))
        problems = []
        names = {"tags": self.tags, "numerics": self.numerics,
                 "bags": self.bag_names}
        for kind, group in names.items():
            for n in group:
                if not isinstance(n, str) or not n.isidentifier():
                    problems.append(
                        f"{kind} field names must be identifiers (got {n!r})"
                    )
            dup = sorted({n for n in group if group.count(n) > 1})
            if dup:
                problems.append(f"duplicate {kind} fields: {dup}")
        overlap = sorted(set(self.tags) & set(self.numerics))
        if overlap:
            problems.append(
                f"fields declared as both tag and numeric: {overlap}"
            )
        overlap = sorted(set(self.bag_names)
                         & (set(self.tags) | set(self.numerics)))
        if overlap:
            problems.append(f"bag fields declared twice: {overlap}")
        for n, w in self.bags:
            if not isinstance(w, int) or isinstance(w, bool) or w < 1:
                problems.append(f"bag field {n!r} needs a width >= 1 "
                                f"(got {w!r})")
        if not (self.tags or self.numerics or self.bags):
            problems.append("schema must declare at least one field")
        if problems:
            raise ValueError(
                "invalid MetadataSchema: " + "; ".join(problems)
            )

    @property
    def bag_names(self) -> tuple[str, ...]:
        return tuple(n for n, _ in self.bags)

    @property
    def fields(self) -> tuple[str, ...]:
        return self.tags + self.bag_names + self.numerics

    @property
    def tag_width(self) -> int:
        """Code columns of ``MetaArrays.tags``: one per tag field, then
        ``width`` per bag field."""
        return len(self.tags) + sum(w for _, w in self.bags)

    def bag_columns(self) -> dict[str, tuple[int, int]]:
        """Bag field -> (first code column, width)."""
        out, col = {}, len(self.tags)
        for n, w in self.bags:
            out[n] = (col, w)
            col += w
        return out

    def to_json(self) -> dict:
        doc = {"tags": list(self.tags), "numerics": list(self.numerics)}
        if self.bags:
            doc["bags"] = dict(self.bags)
        return doc

    @classmethod
    def from_json(cls, obj: dict) -> "MetadataSchema":
        return cls(tags=tuple(obj.get("tags", ())),
                   numerics=tuple(obj.get("numerics", ())),
                   bags=tuple(dict(obj.get("bags", {})).items()))


# ---------------------------------------------------------------- expressions
def _sorted_clauses(clauses):
    return tuple(sorted((f, tuple(sorted(vs))) for f, vs in clauses))


@dataclasses.dataclass(frozen=True)
class FilterExpr:
    """A conjunction of clauses over schema fields. Frozen and hashable:
    it keys the semantic cache's scopes and the engine's pending groups of
    backends that take one predicate per dispatch.

    ``tag_clauses``: ((field, (value, ...)), ...) — field's tag ∈ set.
    ``bag_clauses``: ((field, (value, ...)), ...) — field's bag holds
    every value.
    ``num_clauses``: ((field, lo, hi), ...) — lo <= field <= hi
    (``-inf``/``+inf`` for one-sided ranges). Clauses are sorted so two
    equal predicates hash equal regardless of construction order."""

    tag_clauses: tuple[tuple[str, tuple[str, ...]], ...] = ()
    num_clauses: tuple[tuple[str, float, float], ...] = ()
    bag_clauses: tuple[tuple[str, tuple[str, ...]], ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "tag_clauses",
                           _sorted_clauses(self.tag_clauses))
        object.__setattr__(self, "bag_clauses",
                           _sorted_clauses(self.bag_clauses))
        object.__setattr__(
            self,
            "num_clauses",
            tuple(sorted((f, float(lo), float(hi))
                         for f, lo, hi in self.num_clauses)),
        )
        problems = []
        for kind, clauses in (("tag", self.tag_clauses),
                              ("bag", self.bag_clauses)):
            for f, vs in clauses:
                if not vs:
                    problems.append(
                        f"{kind} clause on {f!r} has an empty value set")
                for v in vs:
                    if not isinstance(v, str):
                        problems.append(
                            f"{kind} clause on {f!r} has a non-string "
                            f"value {v!r}"
                        )
        for f, lo, hi in self.num_clauses:
            if math.isnan(lo) or math.isnan(hi):
                problems.append(f"numeric clause on {f!r} has a NaN bound")
            elif lo > hi:
                problems.append(
                    f"numeric clause on {f!r} has lo > hi ({lo} > {hi})"
                )
        if not (self.tag_clauses or self.num_clauses or self.bag_clauses):
            problems.append("filter must have at least one clause")
        if problems:
            raise ValueError("invalid FilterExpr: " + "; ".join(problems))

    def __and__(self, other: "FilterExpr") -> "FilterExpr":
        if not isinstance(other, FilterExpr):
            return NotImplemented
        return FilterExpr(
            tag_clauses=self.tag_clauses + other.tag_clauses,
            num_clauses=self.num_clauses + other.num_clauses,
            bag_clauses=self.bag_clauses + other.bag_clauses,
        )

    @property
    def fields(self) -> tuple[str, ...]:
        return (tuple(f for f, _ in self.tag_clauses)
                + tuple(f for f, _ in self.bag_clauses)
                + tuple(f for f, _, _ in self.num_clauses))


class Tag:
    """Builder for tag-field clauses: ``Tag("user") == "alice"`` or
    ``Tag("lang").isin("en", "de")``."""

    __slots__ = ("field",)

    def __init__(self, field: str):
        self.field = field

    def __eq__(self, value) -> FilterExpr:  # type: ignore[override]
        return self.isin(value)

    def __hash__(self):  # __eq__ is repurposed; keep Tag hashable
        return hash(("Tag", self.field))

    def isin(self, *values) -> FilterExpr:
        if len(values) == 1 and isinstance(values[0], (list, tuple, set,
                                                       frozenset)):
            values = tuple(values[0])
        return FilterExpr(tag_clauses=((self.field, tuple(values)),))


class Bag:
    """Builder for bag-field clauses: ``Bag("labels").contains_all("beach",
    "sunset")`` passes the vectors whose bag holds every value."""

    __slots__ = ("field",)

    def __init__(self, field: str):
        self.field = field

    def contains_all(self, *values) -> FilterExpr:
        if len(values) == 1 and isinstance(values[0], (list, tuple, set,
                                                       frozenset)):
            values = tuple(values[0])
        return FilterExpr(bag_clauses=((self.field, tuple(values)),))


class Num:
    """Builder for numeric-field clauses: ``Num("ts").between(a, b)``,
    ``.ge(lo)``, ``.le(hi)``."""

    __slots__ = ("field",)

    def __init__(self, field: str):
        self.field = field

    def between(self, lo: float, hi: float) -> FilterExpr:
        return FilterExpr(num_clauses=((self.field, float(lo), float(hi)),))

    def ge(self, lo: float) -> FilterExpr:
        return self.between(lo, math.inf)

    def le(self, hi: float) -> FilterExpr:
        return self.between(-math.inf, hi)


def parse_filter(doc) -> FilterExpr:
    """A predicate from its JSON form: an object of ``field: clause``
    entries, ANDed, where a clause is ``{"all": [v, ...]}`` (the bag
    field holds every value), ``{"in": [v, ...]}`` or a bare string (the
    tag field is one of the values), or ``{"ge": lo, "le": hi}`` with
    either bound (the numeric field lies in the range). Raises
    ``ValueError`` naming what it cannot read."""
    if not isinstance(doc, dict) or not doc:
        raise ValueError("a filter is a non-empty object of field: clause")
    out = []
    for field, clause in doc.items():
        if isinstance(clause, str):
            clause = {"in": [clause]}
        if not isinstance(clause, dict) or not clause:
            raise ValueError(f"filter clause on {field!r} is not an object")
        keys = set(clause)
        if keys == {"all"} or keys == {"in"}:
            vals = clause.get("all", clause.get("in"))
            if not isinstance(vals, list):
                raise ValueError(
                    f"filter clause on {field!r}: {sorted(keys)[0]!r} "
                    "takes a list of values")
            out.append(Bag(field).contains_all(*vals) if "all" in keys
                       else Tag(field).isin(*vals))
        elif keys and keys <= {"ge", "le"}:
            try:
                lo = float(clause.get("ge", -math.inf))
                hi = float(clause.get("le", math.inf))
            except (TypeError, ValueError):
                raise ValueError(
                    f"filter clause on {field!r} has a non-numeric bound")
            out.append(Num(field).between(lo, hi))
        else:
            raise ValueError(
                f"filter clause on {field!r} has keys {sorted(keys)}; "
                "expected 'all', 'in', or 'ge'/'le'")
    expr = out[0]
    for e in out[1:]:
        expr = expr & e
    return expr


# ----------------------------------------------------------------- compiling
def code_slots(n: int) -> int:
    """Code slots of a clause of ``n`` values: the next power of two, at
    least 2, so a one-value and a two-value clause share a shape."""
    return max(2, 1 << max(0, n - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class FilterShape:
    """The static part of a compiled filter: which clauses over which
    columns, and the code slots of each. Hashable: it is a static argument
    of the compiled search, one program per shape.

    ``tag_clauses``: ((column, slots), ...) — the column's code is one of
    the clause's codes. ``bag_clauses``: ((first column, width, slots),
    ...) — the bag's columns hold every code. ``num_clauses``: (column,
    ...), each with one (lo, hi) pair of bounds."""

    tag_clauses: tuple[tuple[int, int], ...] = ()
    bag_clauses: tuple[tuple[int, int, int], ...] = ()
    num_clauses: tuple[int, ...] = ()

    @property
    def n_codes(self) -> int:
        return (sum(s for _, s in self.tag_clauses)
                + sum(s for _, _, s in self.bag_clauses))


class CompiledFilter(NamedTuple):
    """A ``FilterExpr`` resolved against a schema + vocabulary: its
    static ``shape`` and its values, ``codes`` ((shape.n_codes,) int32,
    the clauses' codes in clause order, each padded with ``PAD_CODE``)
    and ``bounds`` ((len(shape.num_clauses), 2) float32 lo/hi)."""

    shape: FilterShape
    codes: np.ndarray
    bounds: np.ndarray

    @property
    def empty(self) -> bool:
        """True when some clause can match nothing (every value of an
        ``isin`` clause, or one value of a bag clause, is unknown): the
        whole conjunction is unsatisfiable."""
        off = 0
        for _, slots in self.shape.tag_clauses:
            if (self.codes[off:off + slots] == PAD_CODE).all():
                return True
            off += slots
        return bool((self.codes[off:] == NO_CODE).any())


def compile_filter(
    expr: FilterExpr,
    schema: MetadataSchema | None,
    vocab: dict[str, tuple[str, ...]],
) -> CompiledFilter:
    """Resolve ``expr`` against ``schema``/``vocab``. Unknown or
    wrong-kind fields are errors — every violation reported in one
    ``ValueError``. Unknown tag *values* match nothing. ``vocab`` maps a
    field to its values (a tuple, codes are positions) or to a
    ``{value: code}`` dict (``code_maps``), which a caller that compiles
    many predicates builds once."""
    if schema is None:
        raise ValueError(
            "index has no MetadataSchema: build(..., schema=, metadata=) "
            "before searching with filter="
        )
    problems = []
    tag_pos = {f: i for i, f in enumerate(schema.tags)}
    num_pos = {f: i for i, f in enumerate(schema.numerics)}
    bag_pos = schema.bag_columns()

    def hint(f, *kinds):
        for name, pos in kinds:
            if f in pos:
                return f" (declared {name})"
        return ""

    def code_list(f, vs, unknown):
        codes = vocab.get(f, ())
        if not isinstance(codes, dict):
            codes = {v: i for i, v in enumerate(codes)}
        return [codes.get(v, unknown) for v in vs]

    tag_clauses, bag_clauses, codes = [], [], []
    for f, vs in expr.tag_clauses:
        if f not in tag_pos:
            problems.append(f"unknown tag field {f!r}"
                            + hint(f, ("numeric", num_pos), ("bag", bag_pos)))
            continue
        known = sorted({c for c in code_list(f, vs, None) if c is not None})
        slots = code_slots(len(known))
        tag_clauses.append((tag_pos[f], slots))
        codes += known + [PAD_CODE] * (slots - len(known))
    for f, vs in expr.bag_clauses:
        if f not in bag_pos:
            problems.append(f"unknown bag field {f!r}"
                            + hint(f, ("tag", tag_pos), ("numeric", num_pos)))
            continue
        want = sorted(set(code_list(f, vs, NO_CODE)))
        slots = code_slots(len(want))
        bag_clauses.append(bag_pos[f] + (slots,))
        codes += want + [PAD_CODE] * (slots - len(want))
    num_clauses, bounds = [], []
    for f, lo, hi in expr.num_clauses:
        if f not in num_pos:
            problems.append(f"unknown numeric field {f!r}"
                            + hint(f, ("tag", tag_pos), ("bag", bag_pos)))
            continue
        num_clauses.append(num_pos[f])
        bounds.append((lo, hi))
    if problems:
        raise ValueError(
            "filter does not match the collection schema: "
            + "; ".join(problems)
        )
    # tag clauses' codes first, then bag clauses': the order masks read
    return CompiledFilter(
        shape=FilterShape(tuple(tag_clauses), tuple(bag_clauses),
                          tuple(num_clauses)),
        codes=np.asarray(codes, np.int32),
        bounds=np.asarray(bounds, np.float32).reshape(len(bounds), 2),
    )


def code_maps(vocab: dict) -> dict[str, dict[str, int]]:
    """Per field ``{value: code}``: ``compile_filter``'s lookups without a
    pass over the vocabulary per predicate."""
    return {f: {v: i for i, v in enumerate(vs)} for f, vs in vocab.items()}


# ----------------------------------------------------------------- evaluation
def filter_mask(shape: FilterShape, codes, bounds, tags, nums):
    """jnp mask over metadata rows: True where every clause passes.
    ``tags`` (..., tag_width) int32, ``nums`` (..., n_num) float32;
    ``codes`` (n_codes,) and ``bounds`` (n_num clauses, 2) are one
    query's values (traced: vmapped beside the queries); ``shape`` is
    static. Missing values (-1 / NaN) never pass."""
    mask = jnp.ones(tags.shape[:-1], bool)
    off = 0
    for col, slots in shape.tag_clauses:
        c = codes[off:off + slots]
        off += slots
        mask = mask & (tags[..., col, None] == c).any(-1)
    for col, width, slots in shape.bag_clauses:
        c = codes[off:off + slots]
        off += slots
        bag = tags[..., col:col + width]
        held = (bag[..., :, None] == c).any(-2) | (c == PAD_CODE)
        mask = mask & held.all(-1)
    for i, col in enumerate(shape.num_clauses):
        x = nums[..., col]
        mask = mask & (x >= bounds[i, 0]) & (x <= bounds[i, 1])  # NaN fails
    return mask


def filter_mask_np(cfilter: CompiledFilter, tags, nums) -> np.ndarray:
    """Numpy twin of :func:`filter_mask` — the post-filter brute-force
    oracle."""
    tags = np.asarray(tags)
    nums = np.asarray(nums)
    mask = np.ones(tags.shape[:-1], bool)
    codes, off = cfilter.codes, 0
    for col, slots in cfilter.shape.tag_clauses:
        mask &= np.isin(tags[..., col], codes[off:off + slots])
        off += slots
    for col, width, slots in cfilter.shape.bag_clauses:
        bag = tags[..., col:col + width]
        for c in codes[off:off + slots]:
            if c != PAD_CODE:
                mask &= (bag == c).any(-1)
        off += slots
    with np.errstate(invalid="ignore"):
        for (lo, hi), col in zip(cfilter.bounds, cfilter.shape.num_clauses):
            x = nums[..., col]
            mask &= (x >= lo) & (x <= hi)
    return mask


# ------------------------------------------------------------------ postings
class Postings(NamedTuple):
    """Per tag and bag field, keyed by its first code column: the
    ascending ORIGINAL ids of the vectors carrying each code, as CSR
    (``offsets[col]`` (V+1,) into ``ids[col]``). Original ids, so the
    exact path's candidates come in id order and its ties resolve to
    the lower id, as an exact reference's do."""

    offsets: dict
    ids: dict

    def rows(self, col: int, code: int) -> np.ndarray:
        off = self.offsets[col]
        if not 0 <= code < len(off) - 1:
            return np.zeros(0, np.int32)
        return self.ids[col][off[code]:off[code + 1]]


def build_postings(schema: MetadataSchema, vocab: dict,
                   tags: np.ndarray) -> Postings:
    """Postings of ``tags`` ((N, tag_width) codes in original-id order)."""
    tags = np.asarray(tags)
    n = tags.shape[0]
    spans = [(i, 1, f) for i, f in enumerate(schema.tags)] + [
        (col, w, f) for f, (col, w) in schema.bag_columns().items()]
    offsets, ids = {}, {}
    for col, width, f in spans:
        block = tags[:, col:col + width].reshape(-1)     # row-major: rows
        rows = np.repeat(np.arange(n, dtype=np.int32), width)
        keep = block >= 0
        block, rows = block[keep], rows[keep]
        order = np.argsort(block, kind="stable")         # rows stay sorted
        v = max(len(vocab.get(f, ())), int(block.max(initial=-1)) + 1)
        offsets[col] = np.concatenate(
            [[0], np.cumsum(np.bincount(block, minlength=v))])
        ids[col] = rows[order]
    return Postings(offsets, ids)


def _intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Sorted unique ``a`` ∩ sorted unique ``b``, by binary searches of
    the shorter in the longer."""
    if len(a) > len(b):
        a, b = b, a
    if not len(a):
        return a
    at = np.minimum(np.searchsorted(b, a), len(b) - 1)
    return a[b[at] == a]


def match_ids(cfilter: CompiledFilter, postings: Postings,
              tags: np.ndarray, nums: np.ndarray) -> np.ndarray:
    """The ascending original ids of the vectors passing ``cfilter``:
    tag clauses from unions of posting lists, bag clauses from
    intersections (shortest first), numeric clauses by evaluating the
    candidates' columns (``tags``/``nums`` in original-id order), and
    every row when the predicate has no tag or bag clause."""
    if cfilter.empty:
        return np.zeros(0, np.int32)
    sets, off = [], 0
    codes = cfilter.codes
    for col, slots in cfilter.shape.tag_clauses:
        parts = [postings.rows(col, int(c)) for c in codes[off:off + slots]
                 if c >= 0]
        sets.append(np.unique(np.concatenate(parts)))
        off += slots
    for col, _, slots in cfilter.shape.bag_clauses:
        sets += [postings.rows(col, int(c)) for c in codes[off:off + slots]
                 if c >= 0]
        off += slots
    if sets:
        sets.sort(key=len)
        out = sets[0]
        for s in sets[1:]:
            out = _intersect(out, s)
    else:
        out = np.arange(np.asarray(nums).shape[0], dtype=np.int32)
    if cfilter.shape.num_clauses and len(out):
        out = out[filter_mask_np(cfilter, np.asarray(tags)[out],
                                 np.asarray(nums)[out])]
    return out.astype(np.int32)


# ------------------------------------------------------------------- encoding
def build_vocab(
    schema: MetadataSchema, columns: dict[str, Any]
) -> dict[str, tuple[str, ...]]:
    """Sorted vocabulary per tag and bag field from the observed values."""
    vocab = {}
    for f in schema.tags:
        vals = columns.get(f) or ()
        vocab[f] = tuple(sorted({str(v) for v in vals if v is not None}))
    for f in schema.bag_names:
        vals = columns.get(f) or ()
        vocab[f] = tuple(sorted({str(v) for bag in vals if bag is not None
                                 for v in bag}))
    return vocab


def normalize_metadata(
    schema: MetadataSchema, metadata, n: int
) -> dict[str, list]:
    """Accept dict-of-columns or list-of-dicts; return dict-of-columns of
    length ``n`` with ``None`` for missing entries (a bag field's entry is
    a list of values). Unknown fields, length mismatches and bags wider
    than their field are errors — every violation in one ValueError."""
    problems = []
    known = set(schema.fields)
    columns: dict[str, list] = {}
    if isinstance(metadata, dict):
        for f, vals in metadata.items():
            if f not in known:
                problems.append(f"unknown metadata field {f!r}")
                continue
            vals = list(vals)
            if len(vals) != n:
                problems.append(
                    f"metadata column {f!r} has {len(vals)} entries for "
                    f"{n} vectors"
                )
                continue
            columns[f] = vals
    else:
        rows = list(metadata)
        if len(rows) != n:
            problems.append(
                f"metadata has {len(rows)} rows for {n} vectors"
            )
        else:
            bad = sorted(
                {f for row in rows for f in row if f not in known}
            )
            if bad:
                problems.append(f"unknown metadata fields {bad}")
            else:
                for f in known:
                    columns[f] = [row.get(f) for row in rows]
    for f, width in schema.bags:
        wide = sum(1 for bag in columns.get(f, ())
                   if bag is not None and len(set(bag)) > width)
        if wide:
            problems.append(f"{wide} bags of {f!r} hold more than its "
                            f"width {width} of distinct values")
    if problems:
        raise ValueError(
            "metadata does not match the schema: " + "; ".join(problems)
        )
    for f in known:
        columns.setdefault(f, [None] * n)
    return columns


def encode_metadata(
    schema: MetadataSchema,
    vocab: dict[str, tuple[str, ...]],
    columns: dict[str, list],
    n: int,
) -> MetaArrays:
    """Dict-of-columns -> packed code arrays (original-id order). Values
    absent from the vocabulary encode to the missing sentinel (-1): they
    can only appear via vocabularies that predate the value, where
    "matches nothing" is the correct semantics. A bag's codes are
    distinct and ascending, padded with -1."""
    tags = np.full((n, schema.tag_width), _MISSING_TAG, np.int32)
    for j, f in enumerate(schema.tags):
        codes = {v: i for i, v in enumerate(vocab.get(f, ()))}
        col = columns.get(f, [None] * n)
        for i, v in enumerate(col):
            if v is not None:
                tags[i, j] = codes.get(str(v), _MISSING_TAG)
    for f, (c0, _) in schema.bag_columns().items():
        codes = {v: i for i, v in enumerate(vocab.get(f, ()))}
        for i, bag in enumerate(columns.get(f, [None] * n)):
            if bag:
                got = sorted({codes[str(v)] for v in bag if str(v) in codes})
                tags[i, c0:c0 + len(got)] = got
    nums = np.full((n, len(schema.numerics)), np.nan, np.float32)
    for j, f in enumerate(schema.numerics):
        col = columns.get(f, [None] * n)
        for i, v in enumerate(col):
            if v is not None:
                nums[i, j] = float(v)
    return MetaArrays(tags=tags, nums=nums)


def decode_metadata(
    schema: MetadataSchema,
    vocab: dict[str, tuple[str, ...]],
    meta: MetaArrays,
) -> dict[str, list]:
    """Inverse of :func:`encode_metadata` (missing -> None; a bag -> the
    list of its values). Used by compaction to re-encode delta metadata
    under a fresh vocabulary."""
    tags = np.asarray(meta.tags)
    nums = np.asarray(meta.nums)
    out: dict[str, list] = {}
    for j, f in enumerate(schema.tags):
        vals = vocab.get(f, ())
        out[f] = [
            vals[c] if 0 <= c < len(vals) else None
            for c in tags[:, j].tolist()
        ]
    for f, (c0, w) in schema.bag_columns().items():
        vals = vocab.get(f, ())
        out[f] = [[vals[c] for c in row if 0 <= c < len(vals)]
                  for row in tags[:, c0:c0 + w].tolist()]
    for j, f in enumerate(schema.numerics):
        col = nums[:, j]
        out[f] = [None if math.isnan(v) else float(v) for v in col.tolist()]
    return out
