"""The configuration's kind (bench/kinds/<kind>.py) holds what depends on
the deployment's shape: ``vectors_l2`` gives the data, request bodies and
judgement the benchmark had before kinds existed, and a deployment of
another shape joins the benchmark as new files alone."""
import hashlib
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, corpus, loadgen, run, spec  # noqa: E402
import test_bench_metrics  # noqa: E402

SEED = 2**31 + 7
# SHA-256 of the corpus, the pool and four request bodies (three of a
# bulk request's size and one single query, in SEED's order) as
# ``corpus.make_corpus``, ``corpus.make_pool``, ``loadgen.encoded`` and
# ``loadgen.body`` made them before they moved into ``vectors_l2``
FULL = {
    "corpus": "a856d38df18841d55d448ad8b9c607ddf9e64089237668610ea70e5e83592495",
    "pool": "7d7b46f36cf7d791ee65c6453747004488378bd91ccc2220139e9c7e06eea23c",
    "bodies": "6ef374a99c30101a27152f2171c28d2e22f9e57b4cb16ff19f4383ae557e5fbc",
}
REHEARSAL = {
    "corpus": "bef7e4eb67a31dcd8449c103ab953aae1cd2189b93592ee7d8f11d39fd868495",
    "pool": "4d9c0960ea9926c845cbae2b332b64c6e6d46f98c130ce1eb5099d7a3f254459",
    "bodies": "047a57f4250bdfa324faea364f56d4c4d0635c43e2d2dcff8720244469f28ffd",
}
CONFIGS = ("bigann128-hbm", "bigann128-budget25")


def _cell(config: str):
    return spec.load_cell(f"{config}.bulk64")


def _sized(cell, size: str):
    cfg, mix = dict(cell.config), dict(cell.traffic)
    if size == "rehearsal":
        cfg["num_vectors"] = run.REHEARSAL["num_vectors"]
        mix["pool"] = run.REHEARSAL["pool"]
    return cfg, mix


def _sha(raw: bytes) -> str:
    return hashlib.sha256(raw).hexdigest()


@pytest.mark.parametrize("size, want", [("full", FULL),
                                        ("rehearsal", REHEARSAL)])
@pytest.mark.parametrize("config", CONFIGS)
def test_vectors_l2_makes_the_same_data_and_bodies(config, size, want):
    cell = _cell(config)
    cfg, mix = _sized(cell, size)
    data = cell.kind.data(cfg, mix)
    pool = data.pool["queries"]
    qpr = mix["queries_per_request"]
    n, body = loadgen.bodies(cell.kind, {"collection": "corpus",
                                         "k": mix["k"]}, data.pool)
    order = corpus.query_order(SEED, 0, n, 3 * qpr)
    bodies = [body(order[i * qpr:(i + 1) * qpr]) for i in range(3)]
    bodies.append(body(order[:1]))
    assert n == len(pool) == mix["pool"]
    assert {"corpus": _sha(data.corpus["vectors"].tobytes()),
            "pool": _sha(pool.tobytes()),
            "bodies": _sha(b"\n".join(bodies))} == want


def _answers(x, pool, qidx, planted, k=10):
    """Exact answers by a direct sort, with faults planted in some rows."""
    q = pool[qidx].astype(np.float64)
    d = ((q[:, None, :] - x[None].astype(np.float64)) ** 2).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    diff = x[ids] - pool[qidx][:, None, :]
    dists = np.sum(diff * diff, axis=-1, dtype=np.float32)
    dists[10] *= np.float32(1 + 1e-3)
    if not planted:
        return ids, dists
    ids[3, 0] = (ids[3, 0] + 1) % len(x)          # an altered id
    ids[30, 9] = -1                                # out of range
    ids[40, 1] = ids[40, 0]                        # a repeated id
    dists[50] = dists[50][::-1].copy()             # not ascending
    ids[60:70] = np.roll(ids[60:70], 1, axis=0)    # another query's answer
    return ids, dists


# check.compare's numbers on these answers before it judged through a kind
@pytest.mark.parametrize("planted, want", [
    (True, {"unanswered": 3, "bad_answers": 3,
            "miss_at_10": 0.05104166666666665,
            "dist_gap": 38.48010728670368, "streamed_vs_resident": 1}),
    (False, {"unanswered": 0, "bad_answers": 0, "miss_at_10": 0.0,
             "dist_gap": 0.0010000731314904199}),
])
def test_the_comparison_through_the_kind_reads_as_before(planted, want):
    cell = _cell("bigann128-budget25")
    cfg, mix = _sized(cell, "rehearsal")
    data = cell.kind.data(cfg, mix)
    x, pool = data.corpus["vectors"], data.pool["queries"]
    qidx = np.concatenate([np.arange(128), corpus.query_order(5, 0, 128, 64)])
    ids, dists = _answers(x, pool, qidx, planted)
    resident = None
    if planted:
        resident = ids.copy()
        resident[100, 0] = (resident[100, 0] + 2) % len(x)
    checks = check.compare(cell.kind, data, qidx, ids, dists,
                           unanswered=want["unanswered"], recall_floor=0.9,
                           resident_ids=resident)
    got = {name: c["value"] for name, c in checks.items()}
    assert got == pytest.approx(want, rel=1e-9)


COSINE_KIND = '''"""Kind ``cosine_unit``: unit-length vectors ranked by cosine.

The corpus and the pool of ``vectors_l2``, each vector scaled to unit
length. The program serves them as it is: its squared-L2 page graph ranks
unit vectors as cosine similarity does (|x - q|^2 = 2 - 2 cos), and the
distance it serves is that squared L2. The truth ranks by the cosine.
"""
import numpy as np

from bench import corpus, reference
from bench.kinds import vectors_l2 as l2

digest_inputs, build, attach = l2.digest_inputs, l2.build, l2.attach
record_bytes, encoded, body = l2.record_bytes, l2.encoded, l2.body


def _unit(a):
    return (a / np.linalg.norm(a, axis=1, keepdims=True)).astype(np.float32)


def data(cfg, mix):
    d = l2.data(cfg, mix)
    return corpus.Data({"vectors": _unit(d.corpus["vectors"])},
                       {"queries": _unit(d.pool["queries"])})


def distances(data, qidx, ids):
    return reference.sq_dists(data.corpus["vectors"],
                              data.pool["queries"][qidx], ids)


def truth(data, asked, k):
    x = np.asarray(data.corpus["vectors"], np.float64)
    q = np.asarray(data.pool["queries"][asked], np.float64)
    ids = np.argsort(-(q @ x.T), axis=1, kind="stable")[:, :k]
    return ids, distances(data, asked, ids)


def control(data, asked, k):
    import ml_dtypes

    x = data.corpus["vectors"].astype(ml_dtypes.bfloat16).astype(np.float32)
    return truth(corpus.Data({"vectors": x}, data.pool), asked, k)
'''

ANSWERED_PER_S = '''"""Queries answered per second of the window, by the engine's count."""


def compute(rec):
    c = rec["counters"]
    done = c["end"]["requests"] - c["start"]["requests"]
    return done / rec["seconds"] if done else None
'''


def _copy_benchmark(dst: Path) -> dict:
    """The benchmark's files in ``dst``; returns {path: bytes} of each."""
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, dst / p, ignore=shutil.ignore_patterns(
            "dbcache", "jaxcache", "traces", "__pycache__"))
    return {p: p.read_bytes() for p in dst.rglob("*") if p.is_file()}


def test_a_deployment_of_another_kind_joins_as_new_files(tmp_path):
    """In a copy of the benchmark, add a kind, a configuration, a mix, a
    cell, a per-layer metric and its expectation as new files and entries
    only; the copy runs the cell's CPU rehearsal correct, and no file
    that was there changed but for the entries added to BENCHMARK.json."""
    root = tmp_path / "checkout"
    root.mkdir()
    before = _copy_benchmark(root)
    old_doc = json.loads((root / "BENCHMARK.json").read_text())

    (root / "bench/kinds/cosine_unit.py").write_text(COSINE_KIND)
    cfg = json.loads((ROOT / "bench/configs/bigann128-hbm.json").read_text())
    cfg.update(name="cosine-unit", kind="cosine_unit",
               recall_floor=0.9, memory_budget_fraction=None)
    (root / "bench/configs/cosine-unit.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/bulk64.json").read_text())
    mix.update(clients=2, queries_per_request=32)
    (root / "bench/traffic/bulk32.json").write_text(json.dumps(mix))
    (root / "bench/metrics/answered_per_s.py").write_text(ANSWERED_PER_S)
    (root / "tests/bench/expected/answered_per_s.json").write_text(
        json.dumps({"value": 64.0, "why": "640 queries in 10 s"}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "cosine-unit", "source": "x",
                           "file": "bench/configs/cosine-unit.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "cosine-unit.bulk32",
                             "config": "cosine-unit", "traffic": "bulk32",
                             "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "answered_per_s", "unit": "queries/s",
                             "better": "higher", "source": "program_counter",
                             "layer": "x", "moves": "qps",
                             "workloads": ["cosine-unit.bulk32"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = spec.load_cell("cosine-unit.bulk32", root=root)
    assert Path(cell.kind.__file__) == root / "bench/kinds/cosine_unit.py"
    assert [m["name"] for m in cell.per_layer] == ["answered_per_s"]
    data = cell.kind.data(cell.config, cell.traffic)
    np.testing.assert_allclose(
        np.linalg.norm(data.corpus["vectors"], axis=1), 1.0, rtol=1e-6)
    every = np.arange(len(data.pool["queries"]))
    control = check.compare(cell.kind, data, every,
                            *cell.kind.control(data, every, 10),
                            unanswered=0, recall_floor=0.9)
    assert not check.passed(control)

    r = run.run_cell(cell, 5, 1.0, False, rehearsal=True,
                     db_cache=tmp_path / "dbcache",
                     t_process=time.monotonic(), log=lambda line: None)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert spec.metric_reader("answered_per_s", root)(
        test_bench_metrics.record()) == pytest.approx(
        test_bench_metrics.expected("answered_per_s", root))

    for p, raw in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == raw, p
    for group, entries in old_doc.items():
        if isinstance(entries, list):
            assert doc[group][:len(entries)] == entries
        else:
            assert doc[group] == entries
