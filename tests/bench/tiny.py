"""A copy of the benchmark at sizes a CPU test can run.

``tiny_copy(dst)`` copies ``BENCHMARK.json`` and ``bench/`` into ``dst``,
links the program's ``src``, and shrinks every configuration and traffic
mix; ``run(dst, cell, ...)`` runs a cell there in a child process pinned to
the CPU, with as many host devices as the cell asks for chips, past the
look for a chip (``tests/bench/tiny_run.py``), and returns its result
line.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
RUNNER = Path(__file__).with_name("tiny_run.py")

TENANCY = {"tenants": 5, "lanes": 2, "classes": 12, "n_max": 24,
           "capacity_factor": 1.3}
DESIGN = {"clusters": 3, "classes": 40}


def tiny_copy(dst: Path) -> Path:
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dst)
    os.symlink(REPO / "src", dst / "src")
    for path in (dst / "bench" / "configs").glob("*.json"):
        conf = json.loads(path.read_text())
        if "tenancy" in conf:
            conf["tenancy"] = dict(TENANCY)
        if "design_space" in conf:
            conf["design_space"].update(DESIGN)
        path.write_text(json.dumps(conf))
    for path in (dst / "bench" / "workloads").glob("*.json"):
        cell = json.loads(path.read_text())
        if cell["driver"] == "served":
            cell.update(rate=40.0, warmup_s=0.5, lead_s=0.3)
        else:
            cell.update(chunk=8 * cell["chunk"] // 64, check_sample=12)
        path.write_text(json.dumps(cell))
    return dst


def run(root: Path, cell: str, *, seed: int = 2**31 + 7,
        seconds: float = 1.0, fault: str = "none", control: bool = False,
        timeout: float = 240) -> dict:
    """The result line of one tiny run (or the control's readings)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64",
                        "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    chips = next(w["chips"] for w in manifest["workloads"]
                 if w["name"] == cell)
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    cmd = [sys.executable, str(RUNNER), str(root), cell, str(seed),
           str(seconds), fault] + (["control"] if control else [])
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
