"""The comparison that decides ``correct``: served answers against the
exact reference.

Every answer of every request sent in the window is compared, once the
window has closed. Four numbers, each with its limit; a run is correct
when none is above its limit:

  unanswered   queries with no answer: an HTTP error, a shed result or a
               malformed body. Limit 0: no cell offers more than the
               frontend admits.
  bad_answers  answers that are not k distinct corpus ids with finite,
               ascending distances. Limit 0.
  miss_at_<k>  1 - mean recall@k against the exact k nearest. The limit
               is the configuration's own: 1 - its stated recall floor.
  dist_gap     the widest gap between a served distance and the exact
               float32 squared distance of the id served with it, over
               the query's exact k-th distance. The page scan computes
               member distances exactly in float32, so sound runs read
               rounding; the limit lies between the readings of sound
               runs and of the bfloat16 control (PERF.md, section 2).

Under a memory budget a fifth number holds the configuration's guarantee
that the budget moves pages and never answers:

  streamed_vs_resident  answers whose ids differ from those the same
               saved index gives the same query with every page in HBM
               (the run's resident witness). Limit 0: a wrong or stale
               page from the host fetch can still give distinct ids with
               exact distances, which the four above may miss.
"""
from __future__ import annotations

import numpy as np

from bench import reference

DIST_GAP_LIMIT = 1e-4


def compare(x: np.ndarray, pool: np.ndarray, qidx: np.ndarray,
            ids: np.ndarray, dists: np.ndarray, *, unanswered: int,
            recall_floor: float, resident_ids: np.ndarray | None = None
            ) -> dict:
    """Judge the answers ``ids``/``dists`` (A, k) served for the pool
    queries ``qidx`` (A,); ``resident_ids`` (A, k) is a memory budget's
    resident witness. Returns {name: {"value", "limit"}}."""
    k = ids.shape[1]
    n = len(x)
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float32)
    in_range = ((ids >= 0) & (ids < n)).all(axis=1)
    srt = np.sort(ids, axis=1)
    distinct = (srt[:, 1:] != srt[:, :-1]).all(axis=1)
    finite = np.isfinite(dists).all(axis=1)
    ascending = (dists[:, 1:] >= dists[:, :-1]).all(axis=1)
    good = in_range & distinct & finite & ascending

    asked = np.unique(qidx)
    truth_ids, truth_d = reference.exact_knn(x, pool[asked], k)
    row = np.searchsorted(asked, qidx)
    hits = (ids[:, :, None] == truth_ids[row][:, None, :]).any(axis=2)
    recall = float(hits.mean()) if len(ids) else 0.0

    safe = np.where(in_range[:, None], ids, 0)
    exact = reference.sq_dists(x, pool[qidx], safe)
    scale = np.maximum(truth_d[row][:, -1:], np.float32(1e-12))
    gap = np.abs(dists.astype(np.float64) - exact) / scale
    # ids out of range and non-finite distances are bad_answers' to count
    gap = np.where(in_range[:, None] & np.isfinite(dists), gap, 0.0)
    widest = float(gap.max()) if len(ids) else 0.0
    checks = {
        "unanswered": {"value": int(unanswered), "limit": 0},
        "bad_answers": {"value": int((~good).sum()), "limit": 0},
        f"miss_at_{k}": {"value": 1.0 - recall,
                         "limit": round(1.0 - recall_floor, 12)},
        "dist_gap": {"value": widest, "limit": DIST_GAP_LIMIT},
    }
    if resident_ids is not None:
        differ = (ids != np.asarray(resident_ids, np.int64)).any(axis=1)
        checks["streamed_vs_resident"] = {"value": int(differ.sum()),
                                          "limit": 0}
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def lines(checks: dict) -> list[str]:
    """One line per number, beside its limit, for standard error."""
    return [
        f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
        f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}"
        for name, c in checks.items()
    ]
