"""Paper Table 5: pre-processing (index build) time breakdown — plus the
lifecycle rows that replace rebuilds in every other process: ``save`` /
``load`` wall time and the on-disk artifact size. Load time is the cost a
serving process pays instead of the full build."""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from benchmarks import common
from repro.core import PageANNIndex


def _dir_bytes(path: str) -> int:
    return sum(
        os.path.getsize(os.path.join(root, name))
        for root, _, names in os.walk(path)
        for name in names
    )


def run() -> list[str]:
    x, _, _ = common.dataset()
    cfg = common.base_cfg()
    t0 = time.perf_counter()
    idx = PageANNIndex.build(x[:4000], cfg)   # fresh build incl. Vamana
    total = time.perf_counter() - t0
    s = idx.stats

    art = tempfile.mkdtemp(prefix="repro_build_overhead_")
    try:
        t1 = time.perf_counter()
        idx.save(art)
        save_s = time.perf_counter() - t1
        art_bytes = _dir_bytes(art)
        page_bytes = os.path.getsize(os.path.join(art, "pages.bin"))
        t2 = time.perf_counter()
        PageANNIndex.load(art)
        load_s = time.perf_counter() - t2
    finally:
        shutil.rmtree(art, ignore_errors=True)

    return [
        f"build_total,{1e6 * total:.0f},n=4000;pages={s.pages};cap={s.capacity}",
        f"build_vamana,{1e6 * s.vamana_s:.0f},share={100 * s.vamana_s / total:.0f}%",
        f"build_grouping,{1e6 * s.grouping_s:.0f},share={100 * s.grouping_s / total:.0f}%",
        f"build_pq,{1e6 * s.pq_s:.0f},share={100 * s.pq_s / total:.0f}%",
        f"build_pack,{1e6 * s.pack_s:.0f},share={100 * s.pack_s / total:.0f}%",
        f"build_lsh,{1e6 * s.lsh_s:.0f},share={100 * s.lsh_s / total:.0f}%",
        f"lifecycle_save,{1e6 * save_s:.0f},artifact_bytes={art_bytes};page_file_bytes={page_bytes}",
        f"lifecycle_load,{1e6 * load_s:.0f},speedup_vs_build={total / load_s:.1f}x",
    ]


def main():
    for r in run():
        print(r)


if __name__ == "__main__":
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
