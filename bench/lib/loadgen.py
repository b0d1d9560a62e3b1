"""Load generator of the served cells: wire tenants in a process of their own.

    python bench/lib/loadgen.py '<json spec>'

Started by ``bench/drivers/served.py`` with ``JAX_PLATFORMS=cpu``: this
process never touches the chip.  Each tenant has its own ``AllocClient``
connection to the ``AllocServer`` under test.  The run:

1. registers every tenant's initial lanes;
2. warm-up, untimed: one event to every tenant (its first flush), then
   ``warmup_s`` seconds of the cell's traffic, and waits for all of it;
3. prints ``WINDOW <t0>`` and, from ``t0`` on, offers exactly
   ``rate * seconds`` events open-loop at their scheduled times, shaped
   as the workload's ``params.arrival`` names (``bench/lib/schedule.py``);
4. prints ``END <t_end>`` at ``t0 + seconds``, waits (untimed) until every
   window event is answered, then checks every answer against the plain
   reference and prints ``RESULT <json>``.

Latency is measured on the client from the scheduled send to the decoded
covering flush report; clocks are ``time.perf_counter`` (CLOCK_MONOTONIC,
shared by every process of the machine).
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def draw_events(spec: dict):
    """Initial tenants, the warm-up and window events, and their times."""
    import numpy as np

    from bench.lib import schedule, tenants

    cfg, seed = spec["config"], spec["seed"]
    rng = np.random.default_rng([seed, 1])
    models = tenants.initial_tenants(
        rng, cfg["tenants"], cfg["lanes"], cfg["classes"], cfg["n_max"],
        cfg["capacity_factor"])
    initial = [m.copy() for m in models]
    theta, n_t = spec["traffic"]["zipf_theta"], cfg["tenants"]
    n_warm = int(round(spec["rate"] * spec["warmup_s"]))
    n_win = int(round(spec["rate"] * spec["seconds"]))
    who = np.concatenate([
        np.arange(n_t),
        schedule.zipf_tenants(rng, n_t, theta, n_warm + n_win)])
    events = tenants.make_events(rng, models, who, spec["traffic"]["mix"],
                                 spec["traffic"]["capacity_jitter"])
    warm_times = np.arange(n_t + n_warm) / spec["rate"]
    win_times = schedule.ARRIVALS[spec["traffic"]["arrival"]](
        rng, n_win, spec["seconds"])
    return initial, events[:n_t + n_warm], events[n_t + n_warm:], \
        warm_times, win_times


def _program_event(ev):
    from repro.core.types import (CapacityChange, ClassArrival,
                                  ClassDeparture, SLAEdit)
    kind = ev[0]
    if kind == "arrival":
        return ClassArrival(lane=ev[1], params=dict(ev[2]))
    if kind == "departure":
        return ClassDeparture(lane=ev[1], slot=ev[2])
    if kind == "edit":
        return SLAEdit(lane=ev[1], slot=ev[2], updates=dict(ev[3]))
    return CapacityChange(lane=ev[1], R=ev[2])


def _scenarios(model):
    """The program's lane objects for a tenant's initial lanes."""
    import numpy as np

    from repro.core.types import derive

    out = []
    for lane in range(model.lanes):
        raw = {f: np.asarray(v, np.float32)
               for f, v in model.lane_raw(lane).items()}
        out.append(derive(**raw, R=np.float32(model.R[lane]),
                          rho_bar=np.float32(model.rho_bar[lane])))
    return out


def say(*words) -> None:
    print(*words, flush=True)


def note(what: str) -> None:
    """A set-up milestone on standard error, with the process's clock."""
    print(f"[loadgen] {time.perf_counter():.3f} {what}", file=sys.stderr,
          flush=True)


async def _offer_all(clients, events, times, t0):
    """Offer ``events`` at ``t0 + times``; returns (tickets, send times)."""
    tickets, sent = [], []
    for (t, ev), at in zip(events, times):
        delay = t0 + at - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        sent.append(time.perf_counter())
        tickets.append(clients[t].offer(f"tenant-{t}", _program_event(ev),
                                        t_submit=t0 + at))
    return tickets, sent


async def _resolve(tickets, timeout):
    """Wait for every ticket; those that never resolve stay unresolved."""
    pending = [asyncio.ensure_future(tk.result()) for tk in tickets]
    done, not_done = await asyncio.wait(pending, timeout=timeout)
    for fut in not_done:
        fut.cancel()
    for fut in done:
        fut.exception()                   # retrieve: a client error counts
    return len(not_done)


async def run(spec: dict) -> dict:
    from repro.serving.client import AllocClient

    from bench.lib import checks

    initial, warm, window, warm_times, win_times = draw_events(spec)
    note(f"{len(warm)} warm-up and {len(window)} window events drawn")
    host, port = spec["host"], spec["port"]
    clients = [await AllocClient.connect(host, port) for _ in initial]
    try:
        for t, (client, model) in enumerate(zip(clients, initial)):
            await client.register_tenant(f"tenant-{t}", _scenarios(model),
                                         n_max=model.n_max)
        note(f"{len(clients)} tenants registered")
        t_warm = time.perf_counter() + 0.1
        warm_tickets, _ = await _offer_all(clients, warm, warm_times, t_warm)
        lost = await _resolve(warm_tickets, spec["drain_timeout_s"])
        if lost:
            raise RuntimeError(f"{lost} warm-up events never answered")
        note("warm-up answered")

        t0 = time.perf_counter() + spec["lead_s"]
        say("WINDOW", repr(t0))
        tickets, sent = await _offer_all(clients, window, win_times, t0)
        t_end = t0 + spec["seconds"]
        await asyncio.sleep(max(0.0, t_end - time.perf_counter()))
        say("END", repr(t_end))
        never = await _resolve(tickets, spec["drain_timeout_s"])
        reports = {t: list(c.reports(f"tenant-{t}"))
                   for t, c in enumerate(clients)}
    finally:
        for client in clients:
            await client.close()

    return checks.served_result(spec, initial, warm, window, t0, t_end,
                                win_times, tickets, sent, reports, never)


def main(argv=None) -> int:
    spec = json.loads((argv or sys.argv[1:])[0])
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    note("started")
    result = asyncio.run(run(spec))
    note("checked")
    say("RESULT", json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
