"""Served cells: the admission daemon over the wire, open loop.

This process holds the chip and runs the system under test as a user
deploys it: one ``CapacityEngine`` behind an ``AllocDaemon`` behind an
``AllocServer`` on loopback.  The tenants live in the load generator
(``bench/lib/loadgen.py``), a child process pinned to the CPU, which
measures latency on the client and checks every answer against the
reference once the window has closed.  This process counts compiles from
the window's start until every window event is answered, and traces the
window when asked.
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time
from pathlib import Path

LOADGEN = Path(__file__).resolve().parents[1] / "lib" / "loadgen.py"
#: Name patterns of the solve programs in a trace.
PROGRAMS = {"solve": ["_solve_batch_jit"]}


def _engine(config: dict):
    from repro.core import (CapacityEngine, FlushPolicy, Policies,
                            RoundingPolicy, SolverConfig)
    return CapacityEngine(
        SolverConfig(residency=config["residency"]),
        Policies(flush=FlushPolicy(max_events=config["flush_max_events"]),
                 rounding=RoundingPolicy(enabled=config["rounding"])))


def _spec(ctx: dict, port: int) -> dict:
    cell, config = ctx["cell"], ctx["config"]
    return {"host": "127.0.0.1", "port": port, "seed": ctx["seed"],
            "seconds": ctx["seconds"], "rate": ctx["rate"],
            "warmup_s": cell["warmup_s"], "lead_s": cell["lead_s"],
            "drain_timeout_s": cell["drain_timeout_s"],
            "traffic": cell["params"], "config": config["tenancy"]}


def run(ctx: dict) -> dict:
    return asyncio.run(_serve(ctx))


async def _serve(ctx: dict) -> dict:
    from repro.serving.allocd import AllocDaemon
    from repro.serving.server import AllocServer

    from bench.lib import checks, harness, trace

    config, meter = ctx["config"], ctx["meter"]
    daemon = AllocDaemon(_engine(config), queue_limit=config["queue_limit"])
    server = AllocServer(daemon, host="127.0.0.1", port=0)
    await server.start()
    print(f"[bench] {time.perf_counter():.3f} server listening",
          file=sys.stderr, flush=True)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = await asyncio.create_subprocess_exec(
        sys.executable, str(LOADGEN), json.dumps(_spec(ctx, server.port)),
        stdout=asyncio.subprocess.PIPE, env=env)
    at_window = window_ann = result = t0 = None
    mem_peak = 0
    try:
        async for raw in proc.stdout:
            word, _, rest = raw.decode().strip().partition(" ")
            if word == "WINDOW":
                t0 = float(rest)
                at_window = meter.snapshot()
                if ctx["trace"]:
                    trace.start(ctx["trace_dir"])
                    await asyncio.sleep(max(0.0, t0 - time.perf_counter()))
                    window_ann = trace.open_window()
            elif word == "END":
                trace.stop(window_ann)
                window_ann = None
            elif word == "RESULT":
                result = json.loads(rest)
                at_end = meter.snapshot()
                mem_peak = harness.memory_peak(ctx["devices"])
        rc = await proc.wait()
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
        trace.stop(window_ann)
        await server.close(drain=True)
    if rc != 0 or result is None:
        raise RuntimeError(f"load generator exited {rc} without a result")

    readings = dict(result["readings"])
    readings["window_compiles"] = float(at_end["compiles"]
                                        - at_window["compiles"])
    values = dict(result["values"])
    values["setup_s"] = t0 - ctx["t_start"]
    print(f"[bench] served at {ctx['rate']:g} events/s: "
          f"{result['attempted']} events, {result['lanes_compared']} lane "
          f"answers compared; compiles in window "
          f"{readings['window_compiles']:.0f}, before it "
          f"{at_window['compiles']} ({at_window['compile_s']:.3f} s, cache "
          f"hits {at_window['hits']}); setup {values['setup_s']:.3f} s",
          file=sys.stderr, flush=True)
    return {"attempted": result["attempted"], "failed": result["failed"],
            "values": values, "programs": PROGRAMS,
            "checks": checks.verdict(readings, config["limits"]),
            "memory_peak_bytes": mem_peak}


def control(ctx: dict, dtype: str) -> dict:
    """The control's readings over this cell's window traffic."""
    from bench.lib import checks
    from bench.lib.loadgen import draw_events
    spec = _spec(ctx, 0)
    initial, warm, window, _, _ = draw_events(spec)
    worst = checks.served_control(initial, warm, window, dtype)
    return {"control": dtype, "workload": ctx["cell"]["name"],
            "seed": ctx["seed"], **worst}
