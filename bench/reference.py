"""Plain exact k-nearest-neighbour search under squared L2, in numpy.

The truth every answer of a run is compared with. It imports nothing of
the program and takes nothing it made. Candidates are ranked by the Gram
expansion in float64, where the cancellation of |q|^2 + |x|^2 - 2 q.x is
far below the gaps between neighbours at these norms; the best
``k + margin`` are then re-scored as sums of squared differences in
float32, the precision the configurations state, and sorted by that.
"""
from __future__ import annotations

import numpy as np


def sq_dists(x: np.ndarray, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """(Q, k) float32 squared L2 from each query to its listed corpus rows,
    as sums of squared differences."""
    diff = x[ids].astype(np.float32) - q.astype(np.float32)[:, None, :]
    return np.sum(diff * diff, axis=-1, dtype=np.float32)


def exact_knn(x: np.ndarray, q: np.ndarray, k: int, *, block: int = 256,
              margin: int = 16) -> tuple[np.ndarray, np.ndarray]:
    """The k nearest corpus rows of each query: ((Q, k) int64 ids,
    (Q, k) float32 squared distances), nearest first."""
    x64 = np.asarray(x, np.float64)
    xx = np.einsum("nd,nd->n", x64, x64)
    m = min(k + margin, len(x))
    ids = np.empty((len(q), k), np.int64)
    dists = np.empty((len(q), k), np.float32)
    for lo in range(0, len(q), block):
        qb = np.asarray(q[lo:lo + block], np.float64)
        gram = xx[None, :] - 2.0 * (qb @ x64.T)
        cand = np.argpartition(gram, m - 1, axis=1)[:, :m]
        d = sq_dists(x, q[lo:lo + block], cand)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        ids[lo:lo + block] = np.take_along_axis(cand, order, axis=1)
        dists[lo:lo + block] = np.take_along_axis(d, order, axis=1)
    return ids, dists


def control_knn(x: np.ndarray, q: np.ndarray,
                k: int) -> tuple[np.ndarray, np.ndarray]:
    """The control: this reference one precision below the configurations'
    float32, with the corpus stored in bfloat16 (the step that would tempt
    a change to the page records) and scored in float32. Its answers have
    to come out as not correct."""
    import ml_dtypes

    xb = np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
    return exact_knn(xb.astype(np.float32), q, k)
