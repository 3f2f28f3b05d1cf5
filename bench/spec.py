"""Find a cell's parts by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each per-layer metric is
named too. Each lives in a file of its own, found by that name:

  configuration   the ``file`` its entry in ``configs`` gives
  traffic mix     ``bench/traffic/<traffic>.json``
  per-layer       ``bench/metrics/<name>.py``, whose ``compute(record)``
                  returns the number, or None where it finds nothing

so a later change adds a cell, a mix or a metric by adding files and
entries, and edits none.
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
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def metric_reader(name: str, root: Path = ROOT):
    """``compute`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path
    )
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute
