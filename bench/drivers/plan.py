"""Planner cells: one operator sweeps a what-if design space, closed loop.

Set-up draws a pool of candidate designs from the seed: ``clusters``
clusters of the paper's Table 5 classes, each evaluated at every point of
the capacity-factor x deadline-scale grid of Figs. 2/3 and 4/5.  It builds
the program's ``Candidate`` objects (scenarios derived on the device, one
jitted call per candidate), shuffles them by the seed and solves one chunk
untimed, which compiles the only program the window uses.  The window then
calls ``solve_plan`` on one chunk after another, back to back, until
``--seconds`` have passed; the rate is every candidate returned over the
whole time of those calls.  A sample of the candidates answered in the
window, drawn from the seed, is compared with the reference afterwards.
"""
from __future__ import annotations

import sys
import time

import numpy as np

#: Name patterns of the solve programs in a trace: the one-chip jit and the
#: lane-sharded one.
PROGRAMS = {"solve": ["_solve_batch_jit", "local_solve"]}


class Pool:
    """The design space's raw inputs, one row per candidate."""

    def __init__(self, seed: int, space: dict):
        from bench.lib import table5
        rng = np.random.default_rng([seed, 2])
        shape = (space["clusters"], space["classes"])
        prof = table5.draw_profiles(rng, shape)
        rho_bar = table5.draw_rho_bar(rng, (space["clusters"],))
        nominal = table5.r_up(table5.raw_fields(prof)).sum(axis=1)
        self.raw, self.R, self.rho_bar, self.coords = [], [], [], []
        for f in space["capacity_factors"]:
            for s in space["deadline_scales"]:
                raw = table5.raw_fields(prof, s)
                for c in range(space["clusters"]):
                    self.raw.append({k: v[c] for k, v in raw.items()})
                    self.R.append(float(table5.f32(f * nominal[c])))
                    self.rho_bar.append(float(rho_bar[c]))
                    self.coords.append({"cluster": c, "capacity_factor": f,
                                        "deadline_scale": s})

    def __len__(self) -> int:
        return len(self.raw)

    def lane(self, i: int):
        return self.raw[i], self.R[i], self.rho_bar[i]

    def candidates(self):
        """The program's candidates, scenarios derived on the device: one
        upload and one jitted call per candidate."""
        import jax
        from repro.core.planning import Candidate
        from repro.core.types import derive
        from bench.lib.table5 import RAW_FIELDS

        @jax.jit
        def derive_packed(rows):
            return derive(*rows[:len(RAW_FIELDS)], R=rows[-2, 0],
                          rho_bar=rows[-1, 0])

        out = []
        for i, raw in enumerate(self.raw):
            n = len(raw["A"])
            rows = np.empty((len(RAW_FIELDS) + 2, n), np.float32)
            rows[:len(RAW_FIELDS)] = [raw[f] for f in RAW_FIELDS]
            rows[-2], rows[-1] = self.R[i], self.rho_bar[i]
            out.append(Candidate(i, self.coords[i], derive_packed(rows)))
        return out


def _config(ctx):
    from repro.core import SolverConfig, lane_mesh
    chips = ctx["cell"]["chips"]
    return SolverConfig(mesh=lane_mesh(chips) if chips > 1 else None)


def run(ctx: dict) -> dict:
    from repro.core import planning

    from bench.lib import checks, harness, trace, workcount

    cell, config, meter = ctx["cell"], ctx["config"], ctx["meter"]
    chunk = cell["chunk"]
    pool = Pool(ctx["seed"], config["design_space"])
    cands = pool.candidates()
    order = np.random.default_rng([ctx["seed"], 3]).permutation(len(cands))
    chunks = [[cands[i] for i in order[k:k + chunk]]
              for k in range(0, len(order) - chunk + 1, chunk)]
    solver = _config(ctx)
    planning.solve_plan(chunks[-1], config=solver, chunk=chunk)

    solved, iters, answered = {}, {}, 0
    window_ann = None
    if ctx["trace"]:
        trace.start(ctx["trace_dir"])
        window_ann = trace.open_window()
    at_window = meter.snapshot()
    t0 = time.perf_counter()
    k = 0
    while True:
        part = chunks[k % len(chunks)]
        rep = planning.solve_plan(part, config=solver, chunk=chunk)
        for j, c in enumerate(part):
            solved[c.index] = {"r": rep.r[j], "total": rep.total[j],
                               "feasible": rep.feasible[j]}
            iters[c.index] = iters.get(c.index, 0) + int(rep.iters[j])
        answered += len(part)
        k += 1
        if time.perf_counter() - t0 >= ctx["seconds"]:
            break
    t_end = time.perf_counter()
    at_end = meter.snapshot()
    trace.stop(window_ann)
    mem_peak = harness.memory_peak(ctx["devices"])

    n = config["design_space"]["classes"]
    ops, nbytes = workcount.alg41_work(n, sum(iters.values()))
    sample = np.random.default_rng([ctx["seed"], 4]).choice(
        sorted(solved), size=min(cell["check_sample"], len(solved)),
        replace=False)
    worst = checks.check_plan(solved, pool, sample)
    readings = {"r_rel_l1": worst["r_rel_l1"],
                "total_rel": worst["total_rel"],
                "feasible_mismatch": float(worst["feasible_mismatch"]),
                "window_compiles": float(at_end["compiles"]
                                         - at_window["compiles"])}
    setup_s = t0 - ctx["t_start"]
    print(f"[bench] plan: {answered} candidates in {k} calls of {chunk} "
          f"over {t_end - t0:.3f} s; {worst['compared']} compared; "
          f"compiles in window {readings['window_compiles']:.0f}, before it "
          f"{at_window['compiles']} ({at_window['compile_s']:.3f} s, cache "
          f"hits {at_window['hits']}); setup {setup_s:.3f} s",
          file=sys.stderr, flush=True)
    return {"attempted": answered, "failed": 0,
            "values": {"candidates_per_s": answered / (t_end - t0),
                       "setup_s": setup_s, "candidates": answered},
            "work": {"ops": ops, "bytes": nbytes},
            "programs": PROGRAMS,
            "checks": checks.verdict(readings, config["limits"]),
            "memory_peak_bytes": mem_peak}


def control(ctx: dict, dtype: str) -> dict:
    """The control's readings: the reference in ``dtype`` in the program's
    place, on the same sample a run compares."""
    from bench.lib import checks, reference
    config, cell = ctx["config"], ctx["cell"]
    pool = Pool(ctx["seed"], config["design_space"])
    sample = np.random.default_rng([ctx["seed"], 4]).choice(
        len(pool), size=cell["check_sample"], replace=False)
    solved = {}
    for i in sample:
        out = reference.equilibrium(*pool.lane(int(i)), dtype=dtype)
        solved[int(i)] = {"r": out["r"], "total": out["total"],
                          "feasible": out["feasible"]}
    worst = checks.check_plan(solved, pool, sample)
    return {"control": dtype, "workload": cell["name"], "seed": ctx["seed"],
            **worst}
