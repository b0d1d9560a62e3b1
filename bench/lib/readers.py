"""Shared arithmetic of the per-layer metric readers (``bench/metrics``).

A reader gets the run's record: ``values`` (host-clock readings of the
driver), ``work`` (algorithmic operations and bytes of the window, where
the driver counts them) and, in a traced run, ``trace`` (the reduction of
:mod:`bench.lib.trace`).  It returns None where it finds nothing to read.
"""
from __future__ import annotations

from typing import Optional


def value(run: dict, name: str) -> Optional[float]:
    v = run.get("values", {}).get(name)
    return None if v is None else float(v)


def solve(run: dict) -> Optional[dict]:
    """The solve programs' device time and runs in the traced window."""
    prog = (run.get("trace") or {}).get("programs", {}).get("solve")
    if not prog or prog["runs"] <= 0 or prog["device_s"] <= 0:
        return None
    return prog


def solve_ms_per_run(run: dict) -> Optional[float]:
    """Device milliseconds of one solve program run (one flush), summed
    over the cell's devices."""
    prog = solve(run)
    return None if prog is None else prog["device_s"] / prog["runs"] * 1e3


def idle_pct(run: dict) -> Optional[float]:
    """Share of the window in which no operation ran, mean over devices."""
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
