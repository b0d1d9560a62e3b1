"""Admission daemon driver: multi-tenant event load + throughput reporting.

    PYTHONPATH=src python -m repro.launch.allocd --tenants 3 --lanes 3 \
        --classes 4 --events 24 --arrival poisson --rate 500 --conformance

Builds one CapacityEngine, registers N tenant windows with the
AllocDaemon, drives per-tenant random event traces open-loop on a Poisson,
flash-crowd, or diurnal arrival schedule, and reports sustained events/sec
plus p50/p99 admission latency — the allocd counterpart of
``repro.launch.serve``.  ``--conformance`` replays every tenant's trace
through an identically-initialised offline ``WindowSession.stream`` and
asserts the daemon's flush-boundary equilibria are bit-equal.

Server mode (the wire transport; see ``docs/OPERATIONS.md``):

    PYTHONPATH=src python -m repro.launch.allocd --listen 127.0.0.1:8753

serves the daemon over the length-prefixed JSON-frame protocol of
``repro.serving.wire`` instead of driving synthetic local tenants —
remote processes register tenants and submit events with
``repro.serving.client.AllocClient`` (walkthrough:
``examples/wire_client.py``).  ``--quota-events`` / ``--quota-lanes``
set the default per-tenant admission budget applied to wire tenants
that register without one.
"""
from __future__ import annotations

import argparse
import asyncio
import sys

from repro._env import force_host_devices, use_compile_cache

# both knobs must be set before jax initializes: the persistent compile
# cache, and (--resident shards tenant state over a lane mesh) the forced
# host-device topology of a bare CPU
use_compile_cache()
if "--resident" in sys.argv or "--devices" in sys.argv:
    force_host_devices()

import jax
import numpy as np

from repro.core import (AdmissionWindow, CapacityEngine, FlushPolicy,
                        Policies, RoundingPolicy, SolverConfig, lane_mesh,
                        sample_event_trace, sample_scenario)
from repro.core.engine import TenantQuota
from repro.serving.allocd import (ARRIVAL_PROFILES, AllocDaemon,
                                  drive_open_loop, interleave_traces)
from repro.serving.server import AllocServer


def make_engine(args):
    flush = (FlushPolicy.deadline(args.deadline_slack,
                                  max_events=args.flush_every)
             if args.deadline_slack is not None
             else FlushPolicy(max_events=args.flush_every))
    resident = getattr(args, "resident", False)
    devices = getattr(args, "devices", None)
    mesh = lane_mesh(devices) if (resident or devices) else None
    return CapacityEngine(
        SolverConfig(mesh=mesh,
                     residency="resident" if resident else "round-trip"),
        Policies(flush=flush,
                 rounding=RoundingPolicy(enabled=args.round)))


def make_lanes(args, tenant: int):
    key = jax.random.PRNGKey(args.seed)
    return [sample_scenario(jax.random.fold_in(key, tenant * 97 + lane),
                            args.classes, capacity_factor=1.3)
            for lane in range(args.lanes)]


def make_window(args, tenant: int) -> AdmissionWindow:
    return AdmissionWindow(make_lanes(args, tenant), n_max=2 * args.classes)


def make_traces(args):
    return {f"tenant-{t}": sample_event_trace(args.seed + 7919 * t,
                                              make_window(args, t),
                                              args.events)
            for t in range(args.tenants)}


def assert_reports_bitequal(name, got, want):
    assert len(got) == len(want), \
        f"{name}: {len(got)} flushes vs offline {len(want)}"
    for i, (a, b) in enumerate(zip(got, want)):
        la = jax.tree_util.tree_flatten(a.fractional)[0]
        lb = jax.tree_util.tree_flatten(b.fractional)[0]
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"{name}: flush {i} diverged from offline replay")
        np.testing.assert_array_equal(np.asarray(a.mask),
                                      np.asarray(b.mask))


async def run_daemon(engine, args, traces):
    daemon = AllocDaemon(engine, queue_limit=args.queue_limit)
    for t in range(args.tenants):
        daemon.add_tenant(f"tenant-{t}", make_window(args, t))
    total = sum(len(tr) for tr in traces.values())
    times = ARRIVAL_PROFILES[args.arrival](args.seed, total, args.rate)
    schedule = interleave_traces(traces, times)
    await daemon.start()
    tickets = await drive_open_loop(daemon, schedule)
    await daemon.shutdown(drain=True)
    return daemon, tickets


async def run_server(engine, args):
    daemon = AllocDaemon(engine, queue_limit=args.queue_limit)
    quota = None
    if args.quota_events is not None or args.quota_lanes is not None:
        quota = TenantQuota(max_queued=args.quota_events,
                            max_lanes=args.quota_lanes)
    host, _, port = args.listen.rpartition(":")
    server = AllocServer(daemon, host=host or "127.0.0.1", port=int(port),
                         default_quota=quota)
    await server.start()
    print(f"[allocd] listening on {server.address[0]}:{server.address[1]} "
          f"(queue_limit={args.queue_limit}, default quota="
          f"{quota or 'none'})", flush=True)
    try:
        await server._server.serve_forever()
    except asyncio.CancelledError:
        pass
    finally:
        await server.close(drain=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--listen", type=str, default=None, metavar="HOST:PORT",
                    help="serve the daemon over the wire protocol instead "
                         "of driving local synthetic tenants")
    ap.add_argument("--quota-events", type=int, default=None,
                    help="default TenantQuota.max_queued for wire tenants "
                         "registering without a quota")
    ap.add_argument("--quota-lanes", type=int, default=None,
                    help="default TenantQuota.max_lanes for wire tenants "
                         "registering without a quota")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--classes", type=int, default=5)
    ap.add_argument("--events", type=int, default=32,
                    help="events per tenant")
    ap.add_argument("--arrival", choices=sorted(ARRIVAL_PROFILES),
                    default="poisson")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="open-loop arrival rate [events/s]")
    ap.add_argument("--flush-every", type=int, default=8)
    ap.add_argument("--deadline-slack", type=float, default=None,
                    help="enable FlushPolicy.deadline with this slack [s]")
    ap.add_argument("--queue-limit", type=int, default=4096)
    ap.add_argument("--resident", action="store_true",
                    help="keep tenant window state device-resident on a "
                         "lane mesh across flushes "
                         "(SolverConfig(residency='resident'))")
    ap.add_argument("--devices", type=int, default=None,
                    help="lane-mesh size for --resident / sharded solves "
                         "(default: every addressable device)")
    ap.add_argument("--round", action="store_true",
                    help="run Algorithm 4.2 integerization at every flush")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--conformance", action="store_true",
                    help="assert bit-equality against offline replays")
    return ap.parse_args(argv)


def main(argv=None):
    """Run the daemon driver (or the wire server); return the exit status:
    non-zero when a flush raised, or when ``--conformance`` could not run."""
    args = parse_args(argv)
    if args.listen is not None:
        try:
            asyncio.run(run_server(make_engine(args), args))
        except KeyboardInterrupt:
            pass
        return 0

    engine = make_engine(args)
    traces = make_traces(args)
    daemon, _ = asyncio.run(run_daemon(engine, args, traces))
    rep = daemon.report()

    total = int(rep["events_folded"])
    print(f"[allocd] {args.arrival}: {rep['submitted']:.0f} events, "
          f"{args.tenants} tenants -> folded {total} in "
          f"{rep['elapsed_s']:.2f}s "
          f"({rep['events_per_sec']:.1f} ev/s incl. compile)")
    print(f"[allocd] admission latency p50 {rep['admission_p50_ms']:.1f} ms"
          f" / p99 {rep['admission_p99_ms']:.1f} ms; "
          f"flushes {rep['flushes']:.0f}; rejected {rep['rejected']:.0f} "
          f"(penalty {rep['rejection_cost']:.2f}); "
          f"flush errors {rep['flush_errors']:.0f}")

    status = 0
    if rep["flush_errors"]:
        print(f"[allocd] FAILED: {rep['flush_errors']:.0f} flushes raised "
              "and their tickets were failed", file=sys.stderr)
        status = 1
    if args.conformance:
        if rep["rejected"]:
            print("[allocd] conformance: NOT RUN (rejections under "
                  "backpressure change the delivered trace)",
                  file=sys.stderr)
            status = 1
        else:
            for name, trace in traces.items():
                t = int(name.split("-")[1])
                offline = engine.open_window(make_window(args, t))
                want = list(offline.stream(trace))
                assert_reports_bitequal(name, daemon.reports(name), want)
            print(f"[allocd] conformance: OK ({args.tenants} tenants "
                  "bit-equal to offline replay)")
    return status


if __name__ == "__main__":
    sys.exit(main())
