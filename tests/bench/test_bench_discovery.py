"""The harness finds configurations, cells, drivers and metrics by name,
and a new one works when added as files and manifest entries only."""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

REPO = tiny.REPO
sys.path.insert(0, str(REPO))
from bench.lib import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(REPO)


def test_every_entry_has_its_files(manifest):
    for w in manifest["workloads"]:
        cell, config = harness.load_cell(REPO, manifest, w["name"])
        assert (REPO / "bench" / "drivers" / f"{cell['driver']}.py").is_file()
        assert config["dtype"] == "float32"
        assert cell["chips"] in config["chips"]
        assert set(config["limits"]) >= {"r_rel_l1", "total_rel",
                                         "window_compiles"}
    for m in manifest["per_layer"]:
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_manifest_keeps_the_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert all((REPO / p).is_dir() for p in manifest["paths"])
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in manifest[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= 1
    pairs = {(w["config"], w["traffic"]) for w in cells.values()}
    assert len(pairs) == len(cells)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        for cell in m["workloads"]:
            reported = [x["name"] for x in
                        harness.end_to_end(manifest, cell)]
            assert m["moves"] in reported, (m["name"], cell)
    for cell in cells:
        assert len(harness.end_to_end(manifest, cell)) >= 2
        assert harness.per_layer(manifest, cell)


def test_added_files_are_picked_up(tmp_path):
    """A configuration, a cell and a per-layer metric added as files plus
    manifest entries: the cell runs and the metric is read."""
    root = tiny.tiny_copy(tmp_path)
    bench = root / "bench"
    conf = json.loads((bench / "configs" / "paper-1000c.json").read_text())
    conf["design_space"]["capacity_factors"] = [1.0]
    (bench / "configs" / "dummy-cfg.json").write_text(json.dumps(conf))
    cell = json.loads((bench / "workloads" / "plan-1000c.json").read_text())
    cell.update(config="dummy-cfg", why="dummy")
    (bench / "workloads" / "dummy-cell.json").write_text(json.dumps(cell))
    (bench / "metrics" / "dummy_per_candidate.py").write_text(
        "def read(run):\n"
        "    return run['values']['setup_s'] / run['values']['candidates']\n")
    m = harness.load_manifest(root)
    m["configs"].append({"name": "dummy-cfg", "source": "test",
                         "file": "bench/configs/dummy-cfg.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "dummy-cell", "config": "dummy-cfg",
                           "traffic": "dummy", "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] == "candidates_per_s":
            e["workloads"].append("dummy-cell")
    m["per_layer"].append({"name": "dummy_per_candidate", "unit": "s",
                           "better": "lower", "source": "host_clock",
                           "layer": "planner", "moves": "candidates_per_s",
                           "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    found, config = harness.load_cell(root, m, "dummy-cell")
    assert config["design_space"]["capacity_factors"] == [1.0]
    layer = harness.per_layer(m, "dummy-cell")
    assert [x["name"] for x in layer] == ["dummy_per_candidate"]
    read = harness.read_layer_metrics(
        root, layer, {"values": {"setup_s": 2.0, "candidates": 4}})
    assert read == {"dummy_per_candidate": {"value": 0.5, "unit": "s"}}

    out = tiny.run(root, "dummy-cell")
    assert out["correct"] is True
    assert set(out["metrics"]) == {"candidates_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_unknown_names_are_errors(manifest):
    with pytest.raises(harness.ManifestError):
        harness.load_cell(REPO, manifest, "no-such-cell")
