"""``bench/run.py`` needs the chip: without a TPU, or outside a checkout of
the program, it exits non-zero and prints no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def _run(root: Path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "plan-1000c", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=root, timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(REPO)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
