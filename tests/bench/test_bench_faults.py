"""Every cell's comparison catches the faults its timed path can have.

Each case runs a tiny copy of a cell on the CPU past the look for a chip
(``tiny_run.py``), with the program broken underneath, and sees
``correct`` come out false; the sound run of each driver comes out true.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_copy(tmp_path_factory.mktemp("tiny"))


CASES = [
    ("serve-32t-poisson", "none", True),
    ("serve-32t-poisson", "altered", False),
    ("serve-32t-poisson", "stale", False),
    ("serve-32t-poisson", "half", False),
    ("plan-1000c", "none", True),
    ("plan-1000c", "altered", False),
    ("plan-1000c", "stale", False),
    ("plan-1000c", "half", False),
    ("plan-1000c-4chip", "none", True),
    ("plan-1000c-4chip", "altered", False),
    ("plan-1000c-4chip", "stale", False),
    ("plan-1000c-4chip", "half", False),
    ("plan-1000c-4chip", "exchange", False),
]


@pytest.mark.parametrize("cell,fault,correct", CASES,
                         ids=[f"{c}-{f}" for c, f, _ in CASES])
def test_fault_decides_correct(root, cell, fault, correct):
    out = tiny.run(root, cell, fault=fault)
    assert out["correct"] is correct, out["checks"]
    assert out["failed"] == 0
    assert out["checks"]["window_compiles"]["value"] == 0
