"""bench/reference.py against a direct numpy sort, and the comparison of
bench/check.py, through the ``vectors_l2`` kind, against its control and
against planted faults."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, corpus, reference, spec  # noqa: E402

K = 10
CFG = {"num_vectors": 1500, "dim": 128, "clusters": 16, "cluster_scale": 0.15}
MIX = {"pool": 96, "query_noise": 0.1}
KIND = spec.load_kind(ROOT / "bench" / "kinds" / "vectors_l2.py")


@pytest.fixture(scope="module")
def data():
    d = KIND.data(CFG, MIX)
    return d.corpus["vectors"], d.pool["queries"]


def direct(x, q, k):
    d = ((q[:, None, :].astype(np.float64) - x[None]) ** 2).sum(-1)
    return np.argsort(d, axis=1, kind="stable")[:, :k]


def test_exact_knn_is_a_direct_sort(data):
    x, q = data
    ids, dists = reference.exact_knn(x, q, K, block=40)
    np.testing.assert_array_equal(ids, direct(x, q, K))
    np.testing.assert_array_equal(dists, reference.sq_dists(x, q, ids))
    assert (np.diff(dists, axis=1) >= 0).all()


def judge(x, q, ids, dists, unanswered=0):
    return check.compare(KIND, corpus.Data({"vectors": x}, {"queries": q}),
                         np.arange(len(q)), ids, dists,
                         unanswered=unanswered, recall_floor=0.9)


def test_exact_answers_pass(data):
    x, q = data
    checks = judge(x, q, *reference.exact_knn(x, q, K))
    assert check.passed(checks)
    assert checks["miss_at_10"]["value"] == 0.0
    assert checks["dist_gap"]["value"] == 0.0


def test_the_bfloat16_control_fails(data):
    x, q = data
    checks = judge(x, q, *reference.control_knn(x, q, K))
    assert not check.passed(checks)
    assert checks["dist_gap"]["value"] > 10 * check.DIST_GAP_LIMIT


def altered_id(ids, dists, n):
    ids = ids.copy()
    ids[:, 0] = (ids[:, 0] + 1) % n
    return ids, dists


def rotated_rows(ids, dists, n):
    return np.roll(ids, 1, axis=0), np.roll(dists, 1, axis=0)


def half_left_out(ids, dists, n):
    h = len(ids) // 2
    return (np.concatenate([ids[:h], ids[:len(ids) - h]]),
            np.concatenate([dists[:h], dists[:len(ids) - h]]))


def padding_ids(ids, dists, n):
    ids, dists = ids.copy(), dists.copy()
    ids[::2], dists[::2] = -1, np.inf
    return ids, dists


@pytest.mark.parametrize("fault", [altered_id, rotated_rows, half_left_out,
                                   padding_ids])
def test_a_wrong_answer_fails(data, fault):
    x, q = data
    checks = judge(x, q, *fault(*reference.exact_knn(x, q, K), len(x)))
    assert not check.passed(checks)


def test_an_answer_that_never_came_fails(data):
    x, q = data
    ids, dists = reference.exact_knn(x, q, K)
    checks = check.compare(KIND, corpus.Data({"vectors": x}, {"queries": q}),
                           np.arange(1, len(q)), ids[1:], dists[1:],
                           unanswered=1, recall_floor=0.9)
    assert checks["unanswered"]["value"] == 1
    assert not check.passed(checks)


def test_every_seed_asks_the_same_data_in_another_order():
    """The data set is fixed; the seed draws only the order of the pool's
    queries, so every seed offers the same work."""
    np.testing.assert_array_equal(KIND.make_corpus(CFG),
                                  KIND.make_corpus(CFG))
    a = corpus.query_order(2**31 + 5, 0, 96, 96 * 3)
    b = corpus.query_order(7, 0, 96, 96 * 3)
    for order in (a, b):
        for rep in order.reshape(3, 96):
            assert sorted(rep) == list(range(96))
    assert (a != b).any()
    np.testing.assert_array_equal(a, corpus.query_order(2**31 + 5, 0, 96,
                                                        96 * 3))
