"""The data of a run: seeded streams, query orders and arrivals.

A configuration's kind (bench/kinds/<kind>.py) makes its corpus and
query pool from a stream of ``rng``; both are one fixed data set, the
same for every seed, and ``--seed`` draws the order in which the senders
ask the pool's queries (and, for an open loop, the order of one fixed set
of gaps). So every seed offers the same work in another order: an index
search's cost depends on the data (a corpus drawn per seed gave
1,871-1,907 queries/s on three seeds where two runs of one seed agreed
within 0.1%; PERF.md, section 6), and a fixed data set is also built into
an index once per checkout instead of once per seed. Each part is drawn
from a stream of its own, so the same seed gives the same inputs whatever
else changes. This module imports numpy only: the load generator, which
never imports JAX, uses it too.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

CORPUS, POOL, ORDER, ARRIVALS = 0, 1, 2, 3
# the corpus, the pool and the open loop's gaps are drawn from this fixed
# seed; only their order follows ``--seed``
BASE_SEED = 0


class Data(NamedTuple):
    """A run's data set as its kind makes it: arrays per corpus row, with
    ``vectors`` (N, dim) among them, and arrays per pool query, with
    ``queries`` (pool, dim) among them, each keyed by its name. The pool's
    arrays are what the load generator is sent."""
    corpus: dict
    pool: dict


def rng(seed: int, stream: int, sub: int = 0) -> np.random.Generator:
    """An independent generator for one part of one seed's data."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % 2**64, stream, sub])
    )


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
