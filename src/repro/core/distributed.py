"""Distributed PageANN: independent sharding over a TPU mesh.

The index is partitioned into S shards (S == size of the ``data`` mesh axis);
each shard is a complete PageANN sub-index over a slice of the vectors.
Queries are sharded over the ``model`` axis (throughput dimension, the
paper's "query threads"). A query executes as:

  local beam search on this device's shard   (shard_map block)
  -> all_gather(k local results) over 'data'
  -> global top-k merge

which is the "independent sharding" design surveyed in the paper's §7,
mapped onto jax-native collectives. The cross-shard merge is one all-gather
of (k ids + k distances) per query — tiny — so the collective roofline term
stays negligible (see EXPERIMENTS.md §Roofline pageann rows).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import compat
from repro.core import search as search_mod
from repro.core.config import PageANNConfig, SearchParams

PAD = -1


class ShardedIndex(NamedTuple):
    """SearchData pytree with a leading shard axis on every array, plus the
    per-shard id->original-id maps (host side)."""

    data: search_mod.SearchData        # every leaf: (S, ...)
    new_to_old: np.ndarray             # (S, P*cap) original ids, PAD padded
    capacity: int


def partition_vectors(x: np.ndarray, num_shards: int, seed: int = 0):
    """Balanced random partition (independent sharding)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(x))
    return np.array_split(perm, num_shards)


def build_sharded_index(
    x: np.ndarray, cfg: PageANNConfig, num_shards: int
) -> ShardedIndex:
    """Build per-shard sub-indexes and stack them to identical shapes."""
    from repro.core.index import PageANNIndex

    parts = partition_vectors(x, num_shards, cfg.seed)
    idxs = [PageANNIndex.build(x[p], cfg) for p in parts]
    return stack_shards(idxs, parts)


def stack_shards(idxs, parts) -> ShardedIndex:
    """Stack already-built per-shard sub-indexes (``PageANNIndex`` each,
    over the id slices in ``parts``) into one ``ShardedIndex`` whose leaves
    carry a leading shard axis — the shard_map input layout.  Ragged shards
    are padded to the largest shard's page count; the pad slots carry
    member_count 0 / PAD ids, and the merge in :func:`make_sharded_search`
    masks them out explicitly."""
    num_shards = len(idxs)
    max_pages = max(i.store.num_pages for i in idxs)
    cap = idxs[0].store.capacity

    def pad_pages(d: search_mod.SearchData, pages: int) -> search_mod.SearchData:
        pad = max_pages - pages

        def padp(a, fill):
            if pad == 0:
                return a
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            return jnp.pad(a, widths, constant_values=fill)

        return d._replace(
            page_recs=padp(d.page_recs, 0.0),
            member_count=padp(d.member_count, 0),
            nbr_ids=padp(d.nbr_ids, PAD),
            nbr_count=padp(d.nbr_count, 0),
            # sharded shards are always fully resident: identity residency
            # over the padded page axis (pad pages map to their zero recs)
            resident_map=jnp.arange(max_pages, dtype=jnp.int32),
        )

    datas = [pad_pages(i.data, i.store.num_pages) for i in idxs]
    # mem_codes are sized P*cap per shard -> pad to max
    nmax = max_pages * cap

    def pad_mem(d):
        padn = nmax - d.mem_codes.shape[0]
        return d._replace(
            mem_codes=jnp.pad(d.mem_codes, [(0, padn), (0, 0)]),
            mem_mask=jnp.pad(d.mem_mask, [(0, padn)]),
        )

    datas = [pad_mem(d) for d in datas]
    # one LSH centre per shard, or none: a shard whose hyperplanes pass
    # through the origin takes a zero centre when another shard has one
    if any(d.lsh_center is not None for d in datas):
        zero = jnp.zeros((datas[0].lsh_planes.shape[0],), jnp.float32)
        datas = [d if d.lsh_center is not None
                 else d._replace(lsh_center=zero) for d in datas]
    # cached_pages may differ in length; pad with a sentinel beyond range
    cmax = max(d.cached_pages.shape[0] for d in datas)
    datas = [
        d._replace(
            cached_pages=jnp.pad(
                d.cached_pages,
                [(0, cmax - d.cached_pages.shape[0])],
                constant_values=np.int32(2**31 - 1) if cmax else 0,
            )
        )
        for d in datas
    ]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *datas)

    n2o = np.full((num_shards, nmax), PAD, np.int64)
    for s, (i, p) in enumerate(zip(idxs, parts)):
        local = i.store.new_to_old  # local original ids within shard slice
        valid = local != PAD
        row = np.full(nmax, PAD, np.int64)
        row[: len(local)][valid] = p[local[valid]]
        n2o[s] = row
    return ShardedIndex(data=stacked, new_to_old=n2o, capacity=cap)


def make_sharded_search(
    mesh: Mesh,
    cfg: PageANNConfig,
    capacity: int,
    k: int,
    *,
    params: SearchParams | None = None,
    shard_axis: str = "data",
    query_axis: str = "model",
    center: bool = False,
):
    """Returns (jitted_fn, in_shardings) executing the sharded search.

    stacked SearchData leaves are sharded P(shard_axis); queries (Q, d) are
    sharded P(query_axis); outputs (Q, k) are sharded P(query_axis).
    ``params`` defaults to the config's search knobs. ``center`` says
    whether the stacked shards carry LSH centres (``lsh_center``).
    """
    p = (params or SearchParams.from_config(cfg)).replace(k=k)
    mode = cfg.memory_mode.value

    def local_search(data_blk, q_blk):
        # data_blk leaves: (1, ...) — this device's shard
        data = jax.tree.map(lambda a: a[0], data_blk)
        res = search_mod.batch_search(
            q_blk, data, p, capacity=capacity, mode=mode
        )
        # Mask pad-slot candidates BEFORE the cross-shard merge.  A ragged
        # partition pads every shard to the largest shard's page count, so a
        # shard-local id can point at a pad slot (slot >= member_count of
        # its page, or a wholly padded page with member_count 0).  The
        # search kernel masks those to inf today, but the merge must not
        # depend on that: a pad candidate that ranked would displace a real
        # candidate from another shard and surface as PAD after
        # ``translate_ids``.  Validity is derivable on-device from
        # member_count alone, so enforce it here.
        page = jnp.clip(res.ids, 0) // capacity
        slot = jnp.clip(res.ids, 0) % capacity
        real = (res.ids >= 0) & (slot < data.member_count[page])
        tagged = jnp.where(real, res.ids, PAD)
        dists = jnp.where(real, res.dists, jnp.inf)
        # gather every shard's candidates for these queries
        all_ids = jax.lax.all_gather(tagged, shard_axis)        # (S, q, k)
        all_d = jax.lax.all_gather(dists, shard_axis)           # (S, q, k)
        all_io = jax.lax.all_gather(res.ios, shard_axis)        # (S, q)
        s, qn, _ = all_ids.shape
        shard_tag = jnp.arange(s, dtype=jnp.int32)[:, None, None]
        flat_ids = (all_ids + shard_tag * 0).transpose(1, 0, 2).reshape(qn, -1)
        flat_tag = jnp.broadcast_to(shard_tag, all_ids.shape).transpose(1, 0, 2).reshape(qn, -1)
        flat_d = all_d.transpose(1, 0, 2).reshape(qn, -1)
        flat_d = jnp.where(flat_ids == PAD, jnp.inf, flat_d)
        order = jnp.argsort(flat_d, axis=1)[:, :k]
        top_ids = jnp.take_along_axis(flat_ids, order, axis=1)
        top_tag = jnp.take_along_axis(flat_tag, order, axis=1)
        top_d = jnp.take_along_axis(flat_d, order, axis=1)
        return top_ids, top_tag, top_d, all_io.sum(0)

    fields = [0] * len(search_mod.SearchData._fields)
    if not center:
        fields[search_mod.SearchData._fields.index("lsh_center")] = None
    data_spec = jax.tree.map(lambda _: P(shard_axis),
                             search_mod.SearchData(*fields))
    in_specs = (data_spec, P(query_axis))
    out_specs = (P(query_axis), P(query_axis), P(query_axis), P(query_axis))

    fn = compat.shard_map(
        local_search, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )
    in_shard = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), data_spec),
        NamedSharding(mesh, P(query_axis)),
    )
    return jax.jit(fn), in_shard


def translate_ids(
    sharded: ShardedIndex, top_ids: np.ndarray, top_tag: np.ndarray
) -> np.ndarray:
    """(Q, k) shard-local reassigned ids + shard tags -> original ids."""
    out = np.full_like(top_ids, PAD, dtype=np.int64)
    valid = top_ids >= 0
    out[valid] = sharded.new_to_old[top_tag[valid], top_ids[valid]]
    return out
