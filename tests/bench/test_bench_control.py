"""The control comes out not correct: the reference computed in bfloat16,
the precision below the configurations' float32, put in the program's
place and read by the same comparison, fails the limits that the program
passes.  At the sizes of ``tiny.py``; the chip readings at the cells' own
sizes are in PERF.md."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

sys.path.insert(0, str(tiny.REPO))
from bench.lib import checks, reference, table5  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_copy(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell,config", [
    ("serve-32t-poisson", "yarn-32t-100c"),
    ("plan-1000c", "paper-1000c"),
    ("plan-1000c-4chip", "paper-1000c")])
def test_control_fails_the_limits(root, cell, config):
    limits = json.loads((root / "bench" / "configs" / f"{config}.json")
                        .read_text())["limits"]
    readings = tiny.run(root, cell, control=True)
    assert readings["control"] == "bfloat16"
    for name in ("r_rel_l1", "total_rel"):
        assert readings[name] > limits[name], (name, readings)
    worst = {k: v for k, v in readings.items() if k in limits}
    assert not checks.passed(checks.verdict(worst, limits))


def test_reference_precisions_order():
    """float32 sits near float64; bfloat16 does not."""
    import numpy as np
    rng = np.random.default_rng(3)
    raw = table5.draw_classes(rng, (60,))
    R = float(table5.f32(0.95 * table5.r_up(raw).sum()))
    ref = reference.equilibrium(raw, R, 1.0)
    for dtype, lo, hi in (("float32", 0.0, 1e-5), ("bfloat16", 1e-4, 1.0)):
        got = reference.equilibrium(raw, R, 1.0, dtype=dtype)
        d = np.abs(got["r"] - ref["r"]).sum() / np.abs(ref["r"]).sum()
        assert lo <= d < hi, (dtype, d)
