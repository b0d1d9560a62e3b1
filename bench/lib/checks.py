"""What decides ``correct``: every answer of the timed path against the plain
reference (:mod:`bench.lib.reference`), number by number, each against a
limit of its own.

Numbers compared, worst over every answer compared in a run:

* ``r_rel_l1``: a cluster's allocation against the reference's, as the L1
  distance over the reference's L1 norm (at least 1);
* ``total_rel``: the cluster's objective (cost + penalty) against the
  reference's, as the gap over the reference's magnitude (at least 1);
* exact counts, limit 0: answers that never came or reported a failure,
  admitted-slot masks and granted slots that differ from the reference's,
  and (planner) feasibility flags that differ.

The limits live in each configuration file, beside the readings they were
set from (``PERF.md`` gives both).
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from bench.lib import reference, stats, tenants


def new_worst() -> dict:
    return {"r_rel_l1": 0.0, "total_rel": 0.0, "mask_mismatch": 0,
            "slot_mismatch": 0, "feasible_mismatch": 0, "compared": 0}


def fold(worst: dict, r, r_ref, total, total_ref) -> None:
    """Fold one cluster's answer into the worst readings: ``r_rel_l1``,
    the L1 distance of the allocations over the reference's L1 norm (at
    least 1), and ``total_rel``, the objective's gap over the reference's
    magnitude (at least 1).  A NaN reads as infinite."""
    r = np.asarray(r, np.float64)
    d = np.sum(np.abs(r - r_ref)) / max(np.sum(np.abs(r_ref)), 1.0)
    g = abs(float(total) - total_ref) / max(abs(total_ref), 1.0)
    for key, x in (("r_rel_l1", d), ("total_rel", g)):
        worst[key] = max(worst[key], float(x) if np.isfinite(x) else math.inf)
    worst["compared"] += 1


def verdict(readings: Dict[str, float], limits: Dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` for every limit; a missing or NaN
    reading fails."""
    out = {}
    for name, limit in limits.items():
        value = readings.get(name, float("nan"))
        out[name] = {"value": value, "limit": limit}
    return out


def passed(checks: dict) -> bool:
    return all(isinstance(c["value"], (int, float))
               and not math.isnan(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def _tenant_events(warm, window):
    """Per tenant: its events in offer order, and whether each is in the
    window (the client numbers a tenant's offers 1, 2, ...)."""
    per: Dict[int, List] = {}
    for in_window, events in ((False, warm), (True, window)):
        for t, ev in events:
            per.setdefault(t, []).append((ev, in_window))
    return per


def check_served(initial, warm, window, reports, *, answers=None) -> dict:
    """Replay each tenant's events through its model and compare every flush
    report that answers a window event with the reference.

    ``reports[t]`` are tenant ``t``'s decoded flush reports in flush order
    (objects with ``tickets``, ``fractional``, ``mask``, ``error``).  With
    ``answers`` (the control), each compared report is replaced by
    ``answers(tenant, model)`` -> (r, total, mask).
    """
    ref = tenants.LaneReference()
    worst = new_worst()
    per = _tenant_events(warm, window)
    for t, model0 in enumerate(initial):
        model = model0.copy()
        evs = per.get(t, [])
        for rep in reports.get(t, []):
            in_window = False
            for cseq, slot in rep.tickets:
                ev, w = evs[cseq - 1]
                in_window |= w
                _, want = model.apply(ev)
                if want is not None and slot != want:
                    worst["slot_mismatch"] += 1
            if not in_window or rep.error is not None:
                continue            # a failed flush fails its tickets
            if answers is None:
                r, total, mask = (rep.fractional.r, rep.fractional.total,
                                  rep.mask)
            else:
                r, total, mask = answers(t, model)
            if not np.array_equal(np.asarray(mask, bool), model.mask):
                worst["mask_mismatch"] += 1
            for lane in range(model.lanes):
                want = ref.lane(t, model, lane)
                fold(worst, r[lane], want["r"], total[lane], want["total"])
    return worst


def served_control(initial, warm, window, dtype: str = "bfloat16") -> dict:
    """The control: the reference in ``dtype`` put in the program's place,
    one flush per event, read by the same comparison."""
    low = tenants.LaneReference(dtype)

    class Report:
        error = None

        def __init__(self, cseq):
            self.tickets = [(cseq, None)]

    def answers(t, model):
        r = np.stack([low.lane(t, model, lane)["r"]
                      for lane in range(model.lanes)])
        total = np.asarray([low.lane(t, model, lane)["total"]
                            for lane in range(model.lanes)])
        return r, total, model.mask

    counts: Dict[int, int] = {}
    reports: Dict[int, list] = {}
    for t, _ in list(warm) + list(window):
        counts[t] = counts.get(t, 0) + 1
        reports.setdefault(t, []).append(Report(counts[t]))
    worst = check_served(initial, warm, window, reports, answers=answers)
    # the control names no slot: granted slots are the program's to prove
    worst["slot_mismatch"] = 0
    return worst


def served_result(spec, initial, warm, window, t0, t_end, win_times, tickets,
                  sent, reports, never) -> dict:
    """The load generator's half of a served run: client-side metrics and
    the comparison of every window answer."""
    lat, done_in_window, failed = [], 0, never
    for tk, at in zip(tickets, win_times):
        if tk.t_done is None or tk.report is None:
            lat.append(float("inf"))
            if tk.t_done is not None:
                failed += 1
            continue
        lat.append(tk.t_done - (t0 + at))
        done_in_window += tk.t_done <= t_end
    lag = [s - (t0 + at) for s, at in zip(sent, win_times)]
    backlog = len(tickets) - done_in_window
    worst = check_served(initial, warm, window, reports)
    readings = {"r_rel_l1": worst["r_rel_l1"],
                "total_rel": worst["total_rel"],
                "unanswered": float(failed),
                "mask_mismatch": float(worst["mask_mismatch"]),
                "slot_mismatch": float(worst["slot_mismatch"])}
    if worst["compared"] == 0:
        readings["r_rel_l1"] = readings["total_rel"] = float("nan")
    return {
        "attempted": len(tickets),
        "failed": failed,
        "values": {
            "admission_p50_ms": _ms(stats.percentile(lat, 50)),
            "admission_p95_ms": _ms(stats.percentile(lat, 95)),
            "events_per_s": stats.rate(done_in_window, spec["seconds"]),
            "gen_lag_p95_ms": _ms(stats.percentile(lag, 95)),
            "backlog_events": float(backlog),
        },
        "readings": readings,
        "lanes_compared": worst["compared"],
    }


def _ms(seconds):
    return None if seconds is None else seconds * 1e3


def check_plan(solved: dict, pool, sample: np.ndarray) -> dict:
    """Compare the sampled candidates' equilibria with the reference.

    ``solved[i]`` holds candidate ``i``'s answer (``r``, ``total``,
    ``feasible``) as ``solve_plan`` returned it; ``pool.lane(i)`` gives its
    raw fields, capacity and unit chip cost.
    """
    worst = new_worst()
    for i in sample:
        want = reference.equilibrium(*pool.lane(int(i)))
        got = solved[int(i)]
        r_ref = np.zeros(len(got["r"]))
        r_ref[:len(want["r"])] = want["r"]
        fold(worst, got["r"], r_ref, got["total"], want["total"])
        worst["feasible_mismatch"] += bool(got["feasible"]) != \
            want["feasible"]
    return worst
