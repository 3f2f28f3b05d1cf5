"""The control of ``correct``: the reference one precision down, judged
by the same comparison as a run.

    python bench/control.py --workload <cell>

It makes the cell's corpus and query pool, answers every pool query (all
that any seed's run asks, in whatever order) with
``reference.control_knn`` (the corpus stored in bfloat16, scored in
float32) in the program's place, and prints the numbers
``check.compare`` reads as one JSON line. The control has to come out as
not correct; its ``dist_gap`` is the upper reading the limit is set
below (PERF.md, section 2). The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import check, corpus, reference, spec  # noqa: E402


def readings(cell) -> dict:
    cfg, mix = cell.config, cell.traffic
    x = corpus.make_corpus(cfg)
    pool = corpus.make_pool(x, mix)
    ids, dists = reference.control_knn(x, pool, mix["k"])
    checks = check.compare(x, pool, np.arange(len(pool)), ids, dists,
                           unanswered=0, recall_floor=cfg["recall_floor"])
    return {"correct": check.passed(checks),
            "checks": {k: c["value"] for k, c in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    print(json.dumps(readings(spec.load_cell(args.workload))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
