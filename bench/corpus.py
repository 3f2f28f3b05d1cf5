"""The data of a run: corpus, query pool, arrivals and query orders.

The corpus and the query pool are one fixed data set, the same for every
seed, and ``--seed`` draws the order in which the senders ask the pool's
queries (and, for an open loop, the order of one fixed set of gaps). So
every seed offers the same work in another order: an index search's cost
depends on the data (a corpus drawn per seed gave 1,871-1,907 queries/s
on three seeds where two runs of one seed agreed within 0.1%; PERF.md,
section 6), and a fixed data set is also built into an index once per
checkout instead of once per seed. Each part is drawn from a stream of
its own, so the same seed gives the same inputs whatever else changes.
The corpus follows the clustered-Gaussian model of
``repro.data.pipeline.clustered_vectors`` (SIFT-like local structure),
written out here so that no change to the program can change the data it
is measured on. This module imports numpy only: the load generator, which
never imports JAX, uses it too.
"""
from __future__ import annotations

import numpy as np

CORPUS, POOL, ORDER, ARRIVALS = 0, 1, 2, 3
# the corpus, the pool and the open loop's gaps are drawn from this fixed
# seed; only their order follows ``--seed``
BASE_SEED = 0


def rng(seed: int, stream: int, sub: int = 0) -> np.random.Generator:
    """An independent generator for one part of one seed's data."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % 2**64, stream, sub])
    )


def make_corpus(cfg: dict) -> np.ndarray:
    """(N, dim) float32: ``clusters`` Gaussian centres, each point one
    centre plus ``cluster_scale`` times standard normal noise."""
    r = rng(BASE_SEED, CORPUS)
    n, dim, c = cfg["num_vectors"], cfg["dim"], cfg["clusters"]
    centres = r.standard_normal((c, dim), dtype=np.float32)
    assign = r.integers(0, c, n)
    noise = r.standard_normal((n, dim), dtype=np.float32)
    return np.ascontiguousarray(
        centres[assign] + np.float32(cfg["cluster_scale"]) * noise
    )


def make_pool(x: np.ndarray, mix: dict) -> np.ndarray:
    """(pool, dim) float32 queries: corpus points plus ``query_noise``
    times standard normal noise."""
    r = rng(BASE_SEED, POOL)
    base = x[r.integers(0, len(x), mix["pool"])]
    noise = r.standard_normal(base.shape, dtype=np.float32)
    return np.ascontiguousarray(base + np.float32(mix["query_noise"]) * noise)


def query_order(seed: int, sub: int, pool: int, count: int) -> np.ndarray:
    """The pool indices one sender asks for, in order: whole permutations
    of the pool, one after another."""
    r = rng(seed, ORDER, sub)
    reps = -(-count // pool)
    return np.concatenate([r.permutation(pool) for _ in range(reps)])[:count]


def arrival_offsets(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Send times of an open loop, in seconds from its start: exponential
    gaps of mean ``1/rate`` covering ``seconds``, the same set for every
    seed and only their order drawn from it."""
    count = int(np.ceil(rate * seconds * 1.5)) + 16
    gaps = rng(BASE_SEED, ARRIVALS).exponential(1.0 / rate, count)
    t = np.cumsum(rng(seed, ARRIVALS).permutation(gaps))
    return t[t < seconds]
