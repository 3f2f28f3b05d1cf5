"""Kind ``tagged_l2`` (the filtered deployment): its data and request
bodies stay what they were, and the comparison through it fails an answer
that breaks its query's predicate."""
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, corpus, loadgen, run, spec  # noqa: E402

CELL = "yfcc192-filter-hbm.tagged-bulk64"
SEED = 2**31 + 7
# SHA-256 of the corpus vectors, the (row, tag) keys, the pool (vectors
# then tags) and one bulk request's body in SEED's order
FULL = {
    "corpus": "f6a4785ca4142e539ec937877c0d43ed7217f456213da8513e072259579ac757",
    "tags": "e150200dd7c701a9623a17f17f9f2e505f5fd5552f22f6537bd105507b58a416",
    "pool": "90c3ddc82647ee8045702c5fb9e0dc7ae2ade3ccbfa2431d005c9844c4d52d9a",
    "body": "50817fac5b4c7bcf06ad3de195a63014c1f5e051efba055aeabb82f058d76bb5",
}
REHEARSAL = {
    "corpus": "13948aaf27be2750e054827f6299842855f2caae9e6dac7b51944dcdd2cb95b3",
    "tags": "113eb1038d4717b68e49a1816dbb2f8b56d566084b3af2139732e8ce4998ee3e",
    "pool": "e67ef051df87f4742694860a188121ce5a2aa2d84b25160ff968152de1cd311c",
    "body": "7bb4ccffd74d09b9a1d77c58d18f58833a68104ddd48eb0f4f3c9052fcee8092",
}


def _sized(size: str):
    cell = spec.load_cell(CELL)
    cfg, mix = dict(cell.config), dict(cell.traffic)
    if size == "rehearsal":
        cfg["num_vectors"] = run.REHEARSAL["num_vectors"]
        mix["pool"] = run.REHEARSAL["pool"]
    return cell, cfg, mix


def _sha(raw: bytes) -> str:
    return hashlib.sha256(raw).hexdigest()


@pytest.fixture(scope="module")
def rehearsal_data():
    cell, cfg, mix = _sized("rehearsal")
    return cell.kind, cell.kind.data(cfg, mix)


@pytest.mark.parametrize("size, want", [("full", FULL),
                                        ("rehearsal", REHEARSAL)])
def test_tagged_l2_makes_the_same_data_and_bodies(size, want):
    cell, cfg, mix = _sized(size)
    data = cell.kind.data(cfg, mix)
    n, body = loadgen.bodies(cell.kind, {"collection": "corpus",
                                         "k": mix["k"]}, data.pool)
    order = corpus.query_order(SEED, 0, n, mix["queries_per_request"])
    assert n == mix["pool"]
    assert {"corpus": _sha(data.corpus["vectors"].tobytes()),
            "tags": _sha(data.corpus["tag_keys"].tobytes()),
            "pool": _sha(data.pool["queries"].tobytes()
                         + data.pool["tags"].tobytes()),
            "body": _sha(body(order))} == want


def test_the_data_keeps_its_stated_shape(rehearsal_data):
    kind, data = rehearsal_data
    x, tags = data.corpus["vectors"], data.pool["tags"]
    assert x.dtype == np.float32 and (x == np.rint(x)).all()
    assert x.min() >= 0 and x.max() <= 255
    # half the pool asks one tag, half two, and every query has k matches
    assert ((tags[:, 1] < 0) == (np.arange(len(tags)) % 2 == 0)).all()
    counts = [len(kind._qualifying(data, i)) for i in range(len(tags))]
    assert min(counts) >= 10
    bags = kind.bags_of(data)
    assert max(len(b) for b in bags) <= 32


def test_a_served_id_without_its_query_tags_fails_dist_gap(rehearsal_data):
    kind, data = rehearsal_data
    every = np.arange(len(data.pool["queries"]))
    ids, dists = kind.truth(data, every, 10)
    sound = check.compare(kind, data, every, ids, dists, unanswered=0,
                          recall_floor=0.9)
    assert check.passed(sound), sound
    assert sound["dist_gap"]["value"] == 0.0
    # plant, as query 0's 10th answer, the nearest vector that lacks one
    # of its tags, with its exact distance: every other check holds
    q = data.pool["queries"][0]
    lacking = np.setdiff1d(np.arange(len(data.corpus["vectors"])),
                           kind._qualifying(data, 0))
    diff = data.corpus["vectors"][lacking] - q
    near = lacking[np.argmin(np.sum(diff * diff, axis=1))]
    planted_ids, planted_d = ids.copy(), dists.copy()
    planted_ids[0, -1] = near
    planted_d[0, -1] = max(dists[0, -2], np.sum(
        (data.corpus["vectors"][near] - q) ** 2, dtype=np.float32))
    bad = check.compare(kind, data, every, planted_ids, planted_d,
                        unanswered=0, recall_floor=0.9)
    assert bad["dist_gap"]["value"] == np.inf
    assert bad["bad_answers"]["value"] == 0
    assert not check.passed(bad)


def test_the_bfloat16_control_is_not_correct(rehearsal_data):
    kind, data = rehearsal_data
    every = np.arange(len(data.pool["queries"]))
    checks = check.compare(kind, data, every, *kind.control(data, every, 10),
                           unanswered=0, recall_floor=0.9)
    assert not check.passed(checks)
    assert checks["dist_gap"]["value"] > check.DIST_GAP_LIMIT
