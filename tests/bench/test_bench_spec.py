"""BENCHMARK.json keeps its contract, and a configuration, a traffic mix
and a per-layer metric dropped in as new files are found by name."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in DOC["workloads"]]


def test_top_level_keys_and_paths():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for p in DOC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= DOC["run_seconds"] <= 51


def test_names_units_and_texts():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in DOC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in DOC["configs"] + DOC["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_loads_and_reports_what_it_must(cell):
    c = spec.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(spec.metric_reader(m["name"]))
    assert c.chips in (1, 4)


def test_new_files_and_entries_are_found_without_editing_any(tmp_path):
    """Copy the benchmark, add a configuration, a mix and a metric as new
    files plus entries, and find all three by name."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("dbcache", "jaxcache",
                                                  "traces", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((ROOT / "bench/configs/bigann128-hbm.json").read_text())
    cfg["clusters"] = 32
    (tmp_path / "bench/configs/new-config.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/bulk64.json").read_text())
    mix["clients"] = 2
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def compute(record):\n    return record['seconds'] * 2\n"
    )
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "new-config", "source": "x",
                           "file": "bench/configs/new-config.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "new-config.new-mix",
                             "config": "new-config", "traffic": "new-mix",
                             "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "new_metric", "unit": "s",
                             "better": "lower", "source": "program_counter",
                             "layer": "x", "moves": "setup_s",
                             "workloads": ["new-config.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = spec.load_cell("new-config.new-mix", root=tmp_path)
    assert cell.config["clusters"] == 32 and cell.traffic["clients"] == 2
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert spec.metric_reader("new_metric", root=tmp_path)(
        {"seconds": 1.5}) == 3.0
    for p, raw in before.items():
        assert p.read_bytes() == raw
