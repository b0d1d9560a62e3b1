"""Run one cell of a tiny benchmark copy on the CPU, past the look for a
chip, with the timed path optionally broken underneath.

    python tiny_run.py <root> <cell> <seed> <seconds> <fault> [control]

Faults (each breaks the program, never the benchmark):

* ``altered``: every answer altered by 1 % where it is produced (the
  daemon's flush report as it is encoded; the planner's report);
* ``stale``: a step that returns its state unchanged (a flush that folds
  no event; a planner call that returns the previous call's answers);
* ``half``: half of each batch left out (the flush report's lanes; the
  planner call's candidates);
* ``exchange``: the lane-sharded solve's results never gathered from the
  other chips: every chip's block of lanes reads as the first chip's.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import sys
from pathlib import Path

import numpy as np


def break_served(fault: str) -> None:
    from repro.core.engine import WindowSession
    from repro.serving import wire
    if fault in ("altered", "half"):
        encode = wire.encode_report

        def broken(report):
            r = np.array(report.fractional.r)
            if fault == "altered":
                r = r * 1.01
            else:
                r[len(r) // 2:] = 0.0
            frac = dataclasses.replace(report.fractional, r=r)
            return encode(dataclasses.replace(report, fractional=frac))
        wire.encode_report = broken
    elif fault == "stale":
        flush = WindowSession.flush

        def stale(self):
            if self._last_report is None:
                return flush(self)
            self.discard_pending()
            return self._last_report
        WindowSession.flush = stale
    else:
        raise ValueError(fault)


def break_exchange() -> None:
    import jax
    from repro.core import sharding
    solve = sharding.solve_sharded_batch

    def local_only(batch, mesh, **kw):
        sol = solve(batch, mesh, **kw)
        per = -(-batch.batch_size // mesh.devices.size)

        def first_block(leaf):
            leaf = np.asarray(leaf)
            reps = -(-leaf.shape[0] // per)
            return np.concatenate([leaf[:per]] * reps)[:leaf.shape[0]]
        return jax.tree_util.tree_map(first_block, sol)
    sharding.solve_sharded_batch = local_only


def break_plan(fault: str) -> None:
    if fault == "exchange":
        return break_exchange()
    from repro.core import planning
    solve_plan = planning.solve_plan
    last = []

    def broken(cands, **kw):
        rep = solve_plan(cands, **kw)
        r, total = rep.r.copy(), rep.total.copy()
        keep = np.ones(len(r), bool)
        if fault == "altered":
            r, total = r * 1.01, total * 1.01
        elif fault == "stale":
            if last:
                r, total = last[0].r, last[0].total
            last[:] = [rep]
        elif fault == "half":
            keep[len(r) // 2:] = False
        else:
            raise ValueError(fault)
        r[~keep], total[~keep] = 0.0, 0.0
        return dataclasses.replace(rep, r=r, total=total)
    planning.solve_plan = broken


def main() -> int:
    ap = argparse.ArgumentParser()
    for a in ("root", "cell", "seed", "seconds", "fault"):
        ap.add_argument(a)
    ap.add_argument("control", nargs="?")
    a = ap.parse_args()
    root = Path(a.root)
    sys.path[:0] = [str(root), str(root / "src")]
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  root / "bench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from bench.lib import harness
    manifest = harness.load_manifest(root)
    cell, config = harness.load_cell(root, manifest, a.cell)
    import jax
    run.use_compile_cache(jax)
    if a.fault != "none":
        if cell["driver"] == "served":
            break_served(a.fault)
        else:
            break_plan(a.fault)
    args = run.parse_args(["--workload", a.cell, "--seed", a.seed,
                           "--seconds", a.seconds]
                          + (["--control", "bfloat16"] if a.control else []))
    return run.run_cell(args, manifest, cell, config,
                        jax.devices()[:cell["chips"]])


if __name__ == "__main__":
    sys.exit(main())
