#!/usr/bin/env python3
"""Smoke test of the allocator's main path on a TPU.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --chips 4         # the path that spans four chips

One process, no children.  With no arguments it runs two phases on one
chip, through the entry points a user calls:

* **engine** — one batch of 64 clusters x 1000 job classes (the top size
  of the paper's Figs. 2/3), drawn by ``sample_scenario`` from ``--seed``,
  solved by ``CapacityEngine.solve`` twice: with the default
  ``SolverConfig()`` and with the fused Pallas iteration
  (``SolverConfig(iter_fn=make_fused_iter_fn())``), whose compiled program
  must hold the kernel.  The two agree lane by lane to ULPs of the lane's
  capacity; four lanes agree with the serial numpy loop of the paper
  (``game.solve_distributed_python``) and sit at or above the exact (P3)
  optimum (``method="centralized"``), both within :data:`F32_TOL`.
* **served** — an ``AllocServer`` on loopback, as
  ``python -m repro.launch.allocd --listen`` runs it, driven by 8
  ``AllocClient`` tenants of 4 lanes x 100 classes (``n_max=200``), 32
  events each, flushed every 8.  Every ticket resolves, nothing is
  rejected, no flush raises, and each tenant's flush reports are
  bit-equal to an offline ``WindowSession.stream`` replay.

``--chips 4`` instead runs only what spans chips: the engine batch solved
lane-sharded over a 4-chip mesh, and a device-resident window session of
the served phase's first tenant, each against the same work on one
device, to the README's 1e-6.

It prints the JAX version, the device, the float dtype, compile and run
seconds per phase and the compile cache's directory and hits, and then,
as its last line and only when every check passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU, or outside a checkout of this repository, it exits non-zero
at once and prints no such line.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ENGINE_LANES, ENGINE_CLASSES = 64, 1000      # paper Figs. 2/3, top size
TENANTS, TENANT_LANES, TENANT_CLASSES = 8, 4, 100
TENANT_EVENTS, FLUSH_K = 32, 8
#: The window width ``repro.launch.allocd`` opens for its tenants.
TENANT_N_MAX = 2 * TENANT_CLASSES
REF_LANES = 4
#: Default vs fused solves: ULPs of float32 at the lane's capacity R (the
#: magnitude of the running sums both reorder).
FUSED_ULPS = 64
#: f32 chip solve vs the serial numpy loop: relative L1 distance of the
#: allocations; and how far below the exact (P3) optimum a total may sit.
F32_TOL = 1e-4
#: Sharded / resident vs one device (README: "match ... to <= 1e-6").
MESH_TOL = 1e-6


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits, from jax.monitoring
    events (a cache hit is counted as a compile of its retrieval time)."""

    def __init__(self, monitoring):
        self.compile_s, self.hits, self.misses = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


def passed(what):
    print(f"[chip_smoke]   ok: {what}", flush=True)


def check(ok, what):
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    passed(what)


def timed(fn):
    """(result, seconds) of ``fn()`` with its device work finished."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def engine_batch(seed):
    import jax
    from repro.core import sample_scenario, stack_scenarios
    key = jax.random.PRNGKey(seed)
    scns = [sample_scenario(jax.random.fold_in(key, i), ENGINE_CLASSES,
                            capacity_factor=0.95)
            for i in range(ENGINE_LANES)]
    return scns, stack_scenarios(scns)


def engine_phase(seed, iter_fn):
    """Default and fused solves of the engine batch, checked against each
    other and, on REF_LANES lanes, against the serial loop and (P3)."""
    import numpy as np
    from repro.core import CapacityEngine, SolverConfig, game
    from repro.core.centralized import solve_centralized
    sys.path.insert(0, str(ROOT / "tests"))
    from _tolerance import ulp_at

    scns, batch = engine_batch(seed)
    cfg_u, cfg_f = SolverConfig(), SolverConfig(iter_fn=iter_fn)
    lowered = game._solve_batch_jit.lower(
        batch, eps_bar=cfg_f.eps_bar, lam=cfg_f.lam,
        max_iters=cfg_f.max_iters, sweep_fn=None, init=None,
        iter_fn=iter_fn)
    kernel = "tpu_custom_call" in lowered.as_text()

    reps = {}
    for name, cfg in (("default", cfg_u), ("fused", cfg_f)):
        engine = CapacityEngine(cfg)
        _, first = timed(lambda: engine.solve(batch).fractional)
        reps[name], again = timed(lambda: engine.solve(batch))
        sol = reps[name].fractional
        print(f"[chip_smoke] engine {name}: {batch.batch_size} lanes x "
              f"{batch.n_max} classes, first call {first:.3f} s, "
              f"warm call {again:.3f} s, iters "
              f"{int(np.min(sol.iters))}..{int(np.max(sol.iters))}",
              flush=True)
    r_u = np.asarray(reps["default"].fractional.r)
    r_f = np.asarray(reps["fused"].fractional.r)
    R = np.asarray(batch.scenarios.R)
    worst = max(float(np.max(np.abs(r_f[b] - r_u[b])))
                / ulp_at(R[b], r_u.dtype) for b in range(len(R)))
    check(kernel, "fused solve program holds the Pallas kernel "
          "(tpu_custom_call)")
    check(worst <= FUSED_ULPS,
          f"default == fused lane by lane: worst {worst:.1f} ulp(R) "
          f"<= {FUSED_ULPS}")
    iters_u = np.asarray(reps["default"].fractional.iters)
    iters_f = np.asarray(reps["fused"].fractional.iters)
    print(f"[chip_smoke]   lanes with equal iteration counts: "
          f"{int(np.sum(iters_u == iters_f))}/{len(iters_u)}", flush=True)

    sol_f = reps["fused"].fractional
    for b in np.linspace(0, len(scns) - 1, REF_LANES).astype(int):
        ref, n_it, _ = game.solve_distributed_python(
            scns[b], eps_bar=cfg_f.eps_bar, lam=cfg_f.lam,
            max_iters=cfg_f.max_iters)
        r_ref = np.asarray(ref.r, np.float64)
        dev = (np.sum(np.abs(r_f[b] - r_ref))
               / max(np.sum(np.abs(r_ref)), 1.0))
        check(dev <= F32_TOL,
              f"lane {b} == serial loop: rel L1 {dev:.2e} <= {F32_TOL:g} "
              f"(iters {int(sol_f.iters[b])} vs {n_it})")
        cent = float(solve_centralized(scns[b]).total)
        gap = (float(sol_f.total[b]) - cent) / max(abs(cent), 1.0)
        check(gap >= -F32_TOL,
              f"lane {b} gap to (P3) optimum {gap:.2e} >= -{F32_TOL:g}")


def served_args(seed, *flags):
    """The served phase's tenants and engine, as flags of
    ``python -m repro.launch.allocd``."""
    from repro.launch import allocd
    return allocd.parse_args([
        "--tenants", str(TENANTS), "--lanes", str(TENANT_LANES),
        "--classes", str(TENANT_CLASSES), "--events", str(TENANT_EVENTS),
        "--flush-every", str(FLUSH_K), "--seed", str(seed), *flags])


async def _serve(args, engine, traces):
    from repro.launch.allocd import make_lanes
    from repro.serving.allocd import AllocDaemon
    from repro.serving.client import AllocClient
    from repro.serving.server import AllocServer

    daemon = AllocDaemon(engine, queue_limit=args.queue_limit)
    server = AllocServer(daemon, host="127.0.0.1", port=0)
    await server.start()
    host, port = server.address

    async def tenant(t):
        name = f"tenant-{t}"
        client = await AllocClient.connect(host, port)
        try:
            await client.register_tenant(name, make_lanes(args, t),
                                         n_max=TENANT_N_MAX)
            tickets = [client.offer(name, ev) for ev in traces[name]]
            acks = [await tk.ack() for tk in tickets]
            await client.drain()
            results = [await tk.result() for tk in tickets]
            return acks, results, client.reports(name)
        finally:
            await client.close()

    try:
        out = await asyncio.gather(*(tenant(t) for t in range(TENANTS)))
    finally:
        await server.close(drain=True)
    return daemon, out


def served_phase(seed):
    """8 wire tenants against one in-process AllocServer, then the
    launcher's ``--conformance`` check of each against an offline replay
    on the same engine."""
    from repro.launch.allocd import (assert_reports_bitequal, make_engine,
                                     make_traces, make_window)

    args = served_args(seed)
    engine, traces = make_engine(args), make_traces(args)
    t0 = time.perf_counter()
    daemon, tenants = asyncio.run(
        asyncio.wait_for(_serve(args, engine, traces), 900))
    seconds = time.perf_counter() - t0
    rep = daemon.report()
    print(f"[chip_smoke] served: {TENANTS} tenants x {TENANT_LANES} lanes x "
          f"{TENANT_CLASSES} classes (n_max {TENANT_N_MAX}), "
          f"{rep['submitted']:.0f} events, {rep['flushes']:.0f} flushes in "
          f"{seconds:.3f} s; admission p50 {rep['admission_p50_ms']:.1f} ms "
          f"p99 {rep['admission_p99_ms']:.1f} ms (compile included)",
          flush=True)
    check(all(all(acks) and all(r is not None for r in results)
              for acks, results, _ in tenants),
          f"all {TENANTS * TENANT_EVENTS} tickets accepted and resolved")
    check(rep["rejected"] == 0 and rep["flush_errors"] == 0,
          f"rejections {rep['rejected']:.0f}, flush errors "
          f"{rep['flush_errors']:.0f}")
    for t, (_, _, got) in enumerate(tenants):
        name = f"tenant-{t}"
        want = list(engine.open_window(make_window(args, t))
                    .stream(traces[name]))
        assert_reports_bitequal(name, got, want)
        passed(f"{name}: {len(got)} wire flush reports bit-equal to the "
               "offline replay")
    return seconds


def close(a, b):
    import numpy as np
    return np.allclose(np.asarray(a), np.asarray(b), rtol=MESH_TOL,
                       atol=MESH_TOL)


def mesh_phase(seed, chips):
    """Lane-sharded solve over a ``chips``-chip lane mesh, and the served
    phase's first tenant as a resident window session on it (as
    ``repro.launch.allocd --resident --devices`` runs it), each against
    the same work on one device."""
    from repro.core import CapacityEngine, SolverConfig, lane_mesh
    from repro.launch.allocd import make_engine, make_traces, make_window

    _, batch = engine_batch(seed)
    one, t_one = timed(lambda: CapacityEngine(SolverConfig()).solve(batch))
    shard, t_shard = timed(lambda: CapacityEngine(
        SolverConfig(mesh=lane_mesh(chips))).solve(batch))
    print(f"[chip_smoke] sharded: {batch.batch_size} lanes x {batch.n_max} "
          f"classes over {chips} chips {t_shard:.3f} s, one "
          f"chip {t_one:.3f} s (compile included)", flush=True)
    check(all(close(getattr(shard.fractional, f), getattr(one.fractional, f))
              for f in ("r", "psi", "total", "aux")),
          f"sharded == one device to {MESH_TOL:g}")

    args = served_args(seed)
    trace = make_traces(args)["tenant-0"]
    flags = {"one": (), "resident": ("--resident", "--devices", str(chips))}
    reports, t_res = {}, 0.0
    for name, extra in flags.items():
        session = make_engine(served_args(seed, *extra)).open_window(
            make_window(args, 0))
        reports[name], seconds = timed(lambda: list(session.stream(trace)))
        if name == "resident":
            t_res = seconds
            check(session.window.is_resident, "window stayed resident")
    print(f"[chip_smoke] resident: {len(trace)} events, "
          f"{len(reports['resident'])} flushes over {chips} "
          f"chips {t_res:.3f} s (compile included)", flush=True)
    check(len(reports["one"]) == len(reports["resident"]) and all(
        close(getattr(a.fractional, f), getattr(b.fractional, f))
        for a, b in zip(reports["resident"], reports["one"])
        for f in ("r", "psi", "total", "aux")),
        f"resident == one device, every flush, to {MESH_TOL:g}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharded and resident paths "
                         "over a 4-chip mesh")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro._env import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX found {len(devices)}", file=sys.stderr)
        return 2
    meter = CompileMeter(jax.monitoring)

    from repro.utils import fdtype
    print(f"[chip_smoke] jax {jax.__version__}; device "
          f"{devices[0].device_kind} x {len(devices)}; float dtype "
          f"{jax.numpy.dtype(fdtype()).name}; compile cache {cache_dir}",
          flush=True)

    def report(phase, before, seconds):
        c, h, m = meter.snapshot()
        print(f"[chip_smoke] phase {phase}: {seconds:.3f} s wall, "
              f"{c - before[0]:.3f} s compiling; compile cache "
              f"{h - before[1]} hits / {m - before[2]} misses", flush=True)

    if args.chips == 4:
        before = meter.snapshot()
        _, seconds = timed(lambda: mesh_phase(args.seed, args.chips))
        report("mesh", before, seconds)
    else:
        from repro.kernels.gnep_iter.ops import make_fused_iter_fn
        before = meter.snapshot()
        _, seconds = timed(lambda: engine_phase(args.seed,
                                                make_fused_iter_fn()))
        report("engine", before, seconds)
        before = meter.snapshot()
        seconds = served_phase(args.seed)
        report("served", before, seconds)
    _, hits, _ = meter.snapshot()
    print(f"[chip_smoke] compile cache {'hit' if hits else 'not hit'} "
          f"({hits} hits) in {cache_dir}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
