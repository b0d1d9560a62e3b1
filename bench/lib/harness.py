"""Finding a run's pieces by name, and assembling its result line.

Everything is found from ``BENCHMARK.json`` and file names, so a later
change adds a configuration, a traffic mix or a metric by adding files and
manifest entries only:

* ``bench/configs/<config>.json``  the deployment (sizes, policies, limits);
* ``bench/workloads/<cell>.json``   the traffic mix and its driver;
* ``bench/drivers/<driver>.py``     ``run(ctx) -> dict``;
* ``bench/metrics/<metric>.py``     ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path
from types import ModuleType
from typing import Optional


class ManifestError(RuntimeError):
    """A cell, configuration, driver or metric that cannot be found."""


def load_manifest(root: Path) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise ManifestError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def _by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise ManifestError(f"BENCHMARK.json has no {what} named {name!r}")


def load_cell(root: Path, manifest: dict, name: str) -> tuple:
    """(cell, config): the manifest entries merged with their files."""
    entry = _by_name(manifest["workloads"], name, "workload")
    conf = _by_name(manifest["configs"], entry["config"], "config")
    data = _json(root / "bench" / "workloads" / f"{name}.json")
    if data.get("config") != entry["config"]:
        raise ManifestError(f"{name}: workload file names config "
                            f"{data.get('config')!r}, BENCHMARK.json "
                            f"{entry['config']!r}")
    config = {**_json(root / conf["file"]), "name": conf["name"]}
    return {**data, **entry}, config


def _json(path: Path) -> dict:
    if not path.is_file():
        raise ManifestError(f"missing {path}")
    return json.loads(path.read_text())


def load_module(path: Path) -> ModuleType:
    """Import a driver or metric file by path (its name may hold dots)."""
    if not path.is_file():
        raise ManifestError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(root: Path, cell: dict) -> ModuleType:
    return load_module(root / "bench" / "drivers" / f"{cell['driver']}.py")


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(manifest: dict, cell_name: str) -> list:
    """The end-to-end metrics this cell reports."""
    return [m for m in manifest["end_to_end"] if _applies(m, cell_name)]


def per_layer(manifest: dict, cell_name: str) -> list:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end(manifest, cell_name)}
    return [m for m in manifest["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def read_layer_metrics(root: Path, metrics: list, run: dict) -> dict:
    """Each metric's reader over the run; a reader that finds nothing
    returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": _number(value, m["name"]),
                              "unit": m["unit"]}
    return out


def _number(value, name) -> float:
    value = float(value)
    if math.isnan(value):
        raise ValueError(f"metric {name} read NaN")
    return value


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices))


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: Optional[dict] = None) -> str:
    """The last line of standard output; ``checks`` comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
