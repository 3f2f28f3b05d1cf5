"""Find a cell's parts by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each per-layer metric is
named too, and each configuration names its kind. Each lives in a file of
its own, found by that name:

  configuration   the ``file`` its entry in ``configs`` gives
  kind            ``bench/kinds/<kind>.py``, the configuration's ``kind``
  traffic mix     ``bench/traffic/<traffic>.json``
  per-layer       ``bench/metrics/<name>.py``, whose ``compute(record)``
                  returns the number, or None where it finds nothing

so a later change adds a cell, a mix, a metric or a deployment of another
shape by adding files and entries, and edits none.

A kind holds what depends on the deployment's shape; run.py, loadgen.py
and check.py call it and keep every step and check to themselves. Its
module imports numpy only at its top (the load generator loads it, and
never imports JAX) and gives these functions, where ``data`` is the
``corpus.Data`` that ``data`` made:

  data(cfg, mix)            the corpus with any per-row attributes, and
                            the query pool with any per-query fields;
                            mix files may carry keys only their kind reads
  digest_inputs(cfg)        what of the configuration the built index
                            depends on (the cache key of a saved index)
  build(cfg, data, path)    build the index and save it at ``path``
  attach(svc, collection, path, cfg, k, memory_budget)
                            attach the saved index to a ``VectorService``
                            (schema, index class, search settings)
  record_bytes(index)       bytes of one page record of the attached index
  encoded(pool)             one JSON fragment per pool query, made once
  body(s, frags, qidx)      the bytes of one /search request asking the
                            pool queries ``qidx``; ``s`` has the
                            ``collection`` and ``k``
  truth(data, asked, k)     the reference's (ids, distances) for the pool
                            queries ``asked``, nearest first
  distances(data, qidx, ids)  the reference's distance of each served id
  control(data, asked, k)   the reference one precision down, which has
                            to come out as not correct (control.py)
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple     # BENCHMARK.json entries this cell reports
    per_layer: tuple
    kind: object          # the configuration's kind module
    root: Path = ROOT     # the checkout its files were found in


def _reports(entry: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    # without the key: every cell that reports the metric it moves
    return entry["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    doc = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in doc["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in doc["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()
    )
    e2e = tuple(m for m in doc["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in doc["per_layer"]
                      if _reports(m, name, names))
    kind = load_kind(root / "bench" / "kinds" / f"{config['kind']}.py")
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer,
                kind, root)


def _module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_').replace('-', '_')}", path
    )
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(path):
    """The kind module at ``path`` (its ``__file__`` is that path)."""
    return _module(Path(path), "bench_kind")


def metric_reader(name: str, root: Path = ROOT):
    """``compute`` of ``bench/metrics/<name>.py``."""
    return _module(root / "bench" / "metrics" / f"{name}.py",
                   "bench_metric").compute
