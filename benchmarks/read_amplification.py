"""Paper Table 1: read amplification (bytes fetched / bytes useful).

PageANN fetches whole pages whose entire content (member vectors + topology
+ on-page compressed neighbors) is consumed by Alg. 2 — amplification ~1 by
construction (padding only). DiskANN-style traversal fetches a 4 KB page per
expanded node but uses only that node's (vector + adjacency) record.

The PageANN "padded" figure is the packed record tile actually DMA'd per
hop (``PageStore.recs``, densely packed members + f32-lane neighbor codes
+ counts — see ``layout.pack_page_records``), so the ratio reports the real
lane-padding overhead of the TPU mapping, not a hypothetical tight packing.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import MemoryMode, recall_at_k
from repro.core import baselines as bl


def run() -> list[str]:
    x, q, truth = common.dataset()
    rows = []

    cfg = common.base_cfg(memory_mode=MemoryMode.DISK_ONLY)
    idx = common.pageann_index(x, cfg, "ra_disk")
    res = idx.search(q, k=10)
    logical = idx.store.logical_page_bytes(cfg)
    padded = idx.store.padded_tile_bytes()
    # every byte of the logical page record is consumed by the search
    ra_pageann = padded / logical
    rows.append(
        f"read_amp_pageann,{ra_pageann:.2f},recall={recall_at_k(res.ids, truth):.3f}"
        f";ios={res.ios.mean():.1f};logical={logical};padded={padded}"
    )

    nbrs, books = common.baseline_data(x)
    data = bl.make_baseline_data(x, nbrs, books)
    bres = bl.diskann_search(jnp.asarray(q), data, beam=64, k=10, max_hops=64)
    used = x.shape[1] * 4 + nbrs.shape[1] * 4         # vector + adjacency
    ra_diskann = 4096 / used
    rows.append(
        f"read_amp_diskann,{ra_diskann:.2f},recall={recall_at_k(np.asarray(bres.ids), truth):.3f}"
        f";ios={np.asarray(bres.ios).mean():.1f};used_per_read={used}"
    )

    # Starling-style: co-located pages, opportunistic full-page use on hit
    from repro.core.page_graph import group_pages

    g = group_pages(x, nbrs, capacity=idx.store.capacity, h=2)
    sdata = bl.make_baseline_data(x, nbrs, books, page_of=g.page_of)
    sres = bl.starling_search(jnp.asarray(q), sdata, beam=64, k=10, max_hops=64)
    # unique-page reads; each page contributes ~capacity co-located vectors,
    # but topology still requires per-node records -> partial utility
    util = (idx.store.capacity * x.shape[1] * 4) / 4096
    rows.append(
        f"read_amp_starling,{1.0 / min(util, 1.0):.2f},recall="
        f"{recall_at_k(np.asarray(sres.ids), truth):.3f};ios={np.asarray(sres.ios).mean():.1f}"
    )
    return rows


def main():
    for r in run():
        print(r)


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
