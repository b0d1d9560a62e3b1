"""The Alg. 4.1 work count is the one ``benchmarks/roofline.py`` states
(``run_fused_iter``: ``6 * B * Nc * n`` operations and
``itemsize * B * (3 n + 4 Nc)`` bytes per iteration, ``Nc = n + 2``), and
the least time takes the larger of its two bounds."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.lib import workcount  # noqa: E402


@pytest.mark.parametrize("n,iters", [(17, 1), (100, 3), (1000, 7)])
def test_work_matches_the_roofline_formula(n, iters):
    nc = n + 2
    ops, nbytes = workcount.alg41_work(n, iters)
    assert ops == 6.0 * nc * n * iters
    assert nbytes == 4.0 * (3 * n + 4 * nc) * iters


def test_least_time_names_its_bound():
    peak = workcount.peaks("TPU v5 lite")
    ops, nbytes = workcount.alg41_work(1000, 1)
    t, bound = workcount.least_time(ops, nbytes, peak)
    assert t == max(ops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    assert bound == "memory"
    t, bound = workcount.least_time(1e15, 1.0, peak)
    assert bound == "compute" and t == 1e15 / peak["flops_per_s"]


def test_an_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        workcount.peaks("no such chip")
