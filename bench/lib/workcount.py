"""Algorithmic work of Algorithm 4.1's candidate middle, and its least time.

Per best-reply iteration of one cluster of ``n`` classes the Resource
Manager sweeps ``Nc = n + 2`` candidate prices over every class: about six
operations per (candidate, class) cell (the admission compare, two adds of
the prefix sum, the two compares of the clip, the multiply-add of the
revenue), and at least the three per-class streams and the four
per-candidate rows moved once.  The count is the same whichever
implementation runs the middle, the default jnp path or a fused kernel.
"""
from __future__ import annotations

import json
from pathlib import Path

OPS_PER_CELL = 6.0
PEAKS_FILE = Path(__file__).with_name("peaks.json")


def alg41_work(n: int, iters: int, itemsize: int = 4) -> tuple:
    """(operations, bytes) of ``iters`` iterations at ``n`` classes."""
    nc = n + 2
    return (OPS_PER_CELL * nc * n * iters,
            float(itemsize) * (3 * n + 4 * nc) * iters)


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def least_time(ops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of ops at the compute peak and bytes at
    the memory bandwidth, and which of the two it is."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
