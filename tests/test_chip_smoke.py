"""``chip_smoke.py`` off the chip: it must fail at once, print no result.

The script's phases only mean something on a TPU; on any other backend, or
when it is run outside a checkout of the repository, it must exit non-zero
within seconds and never print its ``{"ok": true, ...}`` line.  Also where
it (and the launchers) put JAX's persistent compilation cache.
"""
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_fast_without_tpu(where, tmp_path):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    t0 = time.perf_counter()
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    elapsed = time.perf_counter() - t0
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert '"ok"' not in proc.stdout
    assert elapsed < 30, f"took {elapsed:.1f} s to fail"


@pytest.mark.parametrize("from_outside", [True, False])
def test_compile_cache_dir(from_outside, monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is used as it is when set; otherwise
    the cache goes to the one fixed, gitignored directory of the checkout."""
    from repro._env import use_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    want = str(tmp_path)
    if not from_outside:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(ROOT / ".jax_cache")
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    assert use_compile_cache() == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
