#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, its driver and
its per-layer metrics are found by name (``bench/lib/harness.py``).  The
run needs a TPU: without one, or with fewer chips than the cell asks for,
it exits with code 2 and prints no result.  It prints each number compared
with the reference beside its limit as the last lines of standard error,
and one JSON object as the last line of standard output.

Further options serve the benchmark's own calibration, not its runs:

    --knee-sweep R1,R2,...   a served cell at each offered rate [events/s]
    --control bfloat16       the reference in bfloat16 in the program's
                             place, read by the same comparison
    --keep-trace DIR         keep a copy of the traced run's trace
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: JAX's persistent compilation cache: a fixed path inside the checkout, so
#: that only a cell's first run there compiles.
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--knee-sweep", default=None, metavar="R1,R2,...")
    ap.add_argument("--control", default=None, choices=("bfloat16",))
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's xplane file into DIR")
    return ap.parse_args(argv)


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def use_compile_cache(jax) -> None:
    """Persistent cache in the checkout, holding every program however
    small: the daemon's per-flush programs compile in well under the
    default one-second threshold and would otherwise compile again in
    every process."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import harness
    try:
        manifest = harness.load_manifest(ROOT)
        cell, config = harness.load_cell(ROOT, manifest, args.workload)
    except (harness.ManifestError, json.JSONDecodeError) as exc:
        return fail(str(exc))
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program under {ROOT / 'src'}: run from a checkout")

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    use_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, JAX "
                    f"found {len(devices)}")
    if jax.config.jax_enable_x64:
        return fail("x64 is on: the configurations run float32")
    print(f"[bench] {args.workload}: jax {jax.__version__}, "
          f"{devices[0].device_kind} x {len(devices)}, dtype "
          f"{config['dtype']}, seed {args.seed}, {args.seconds:g} s",
          file=sys.stderr, flush=True)

    return run_cell(args, manifest, cell, config, devices[:cell["chips"]])


def run_cell(args, manifest, cell, config, devices) -> int:
    """Everything after the look for the chip: drive the cell, report it."""
    import jax

    from bench.lib import harness
    from bench.lib.compile_meter import CompileMeter

    ctx = {"cell": cell, "config": config,
           "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "devices": devices,
           "meter": CompileMeter(jax.monitoring), "t_start": T_START,
           "trace_dir": OUT_DIR / "trace", "rate": cell.get("rate"),
           "keep_trace": args.keep_trace}
    drv = harness.driver(ROOT, cell)
    if args.control:
        print(json.dumps(drv.control(ctx, args.control)), flush=True)
        return 0
    if args.knee_sweep:
        return knee_sweep(drv, ctx, [float(r) for r in
                                     args.knee_sweep.split(",")])
    if ctx["trace"]:
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    return report(manifest, cell, ctx, drv.run(ctx))


def report(manifest, cell, ctx, run) -> int:
    from bench.lib import checks, harness
    from bench.lib import trace as tr

    devices = ctx["devices"]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    breakdown = None
    run["device_kind"] = devices[0].device_kind
    if ctx["trace"]:
        path = tr.newest_trace(str(ctx["trace_dir"]))
        if ctx.get("keep_trace"):
            os.makedirs(ctx["keep_trace"], exist_ok=True)
            shutil.copy(path, ctx["keep_trace"])
        t_read = time.perf_counter()
        red = tr.reduce(tr.load(path), run["programs"])
        print(f"[bench] trace of {os.path.getsize(path)} bytes reduced in "
              f"{time.perf_counter() - t_read:.3f} s", file=sys.stderr,
              flush=True)
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
        run["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        metrics = harness.read_layer_metrics(
            ROOT, harness.per_layer(manifest, cell["name"]), run)
    else:
        metrics = {}
        for m in harness.end_to_end(manifest, cell["name"]):
            value = run["values"].get(m["name"])
            if value is None:
                raise RuntimeError(f"{cell['name']} did not measure "
                                   f"{m['name']}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    verdict = run["checks"]
    for name, c in verdict.items():
        ok = "ok" if checks.passed({name: c}) else "FAILED"
        print(f"[bench] check {name}: {c['value']!r} <= {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(harness.result_line(
        correct=checks.passed(verdict), attempted=run["attempted"],
        failed=run["failed"], metrics=metrics, device=device,
        breakdown=breakdown, checks=verdict), flush=True)
    return 0


def knee_sweep(drv, ctx, rates) -> int:
    """The served cell at each rate, one after another in this process."""
    from bench.lib import checks
    rows = []
    for r in rates:
        run = drv.run({**ctx, "rate": r})
        v = run["values"]
        rows.append({"rate": r, "setup_s": v["setup_s"],
                     "correct": checks.passed(run["checks"]),
                     "admission_p50_ms": v["admission_p50_ms"],
                     "admission_p95_ms": v["admission_p95_ms"],
                     "events_per_s": v["events_per_s"],
                     "backlog_events": v["backlog_events"],
                     "gen_lag_p95_ms": v["gen_lag_p95_ms"],
                     "window_compiles": run["checks"]["window_compiles"]
                     ["value"]})
        print("[sweep] " + json.dumps(rows[-1]), file=sys.stderr, flush=True)
    print(json.dumps({"sweep": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
