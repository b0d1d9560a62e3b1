"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

The window is the span of the host annotation :data:`WINDOW` that the
drivers open around the traced part of their window.  Within it, per
device plane (``/device:TPU:<i>``):

* busy time: the union of the intervals in which an operation ran (the
  plane's ``XLA Ops`` line, or every line where it has none);
* per-program time: the summed durations of the plane's ``XLA Modules``
  events whose name contains a given pattern, and their count;
* idle gaps: the spaces in which no device was busy, each named by the
  innermost host event that covers its middle.

Planes, lines and events are read through their ``name``, ``lines``,
``events``, ``start_ns`` and ``end_ns`` attributes, as
``jax.profiler.ProfileData`` gives them.  Host and device timestamps in a
trace share one clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Device operations are named by their HLO text; the breakdown keeps the
#: head of it (the instruction, its shape and kind).
NAME_CHARS = 120


def start(trace_dir) -> None:
    """Start the profiler, without its Python tracer (it would slow every
    call of the host path it measures)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def open_window():
    """Open the :data:`WINDOW` annotation; pass the result to :func:`stop`."""
    import jax
    ann = jax.profiler.TraceAnnotation(WINDOW)
    ann.__enter__()
    return ann


def stop(window) -> None:
    """Close the window annotation and stop the profiler (no-op on None)."""
    if window is not None:
        import jax
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()


def newest_trace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> list:
    """The trace's planes (reads with nothing but JAX)."""
    from jax.profiler import ProfileData
    return list(ProfileData.from_file(path).planes)


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _is_device(plane) -> bool:
    return plane.name.startswith(DEVICE_PREFIX)


def _host_events(planes):
    for plane in planes:
        if not _is_device(plane):
            for line in plane.lines:
                for ev in line.events:
                    yield ev.name, ev.start_ns, ev.end_ns


def window_span(planes: Sequence) -> Tuple[float, float]:
    """(start, end) of the driver's window annotation, in ns."""
    spans = [(s, e) for n, s, e in _host_events(planes) if n == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return max(spans, key=lambda se: se[1] - se[0])


def _device_lines(plane) -> Dict[str, list]:
    return {line.name: [(ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events] for line in plane.lines}


def reduce(planes: Sequence, programs: Dict[str, Sequence[str]],
           top: int = 10) -> dict:
    """Device metrics of the window.

    Parameters
    ----------
    planes : sequence
        :func:`load` output.
    programs : dict
        Label -> name patterns of the jitted programs to time; a module
        event counts for a label when its name contains any pattern.

    Returns
    -------
    dict
        ``window_s``; ``devices``; ``busy_s`` (mean over devices);
        ``programs`` (label -> {"device_s": summed over devices, "runs":
        mean runs per device}); ``device_ops`` and ``idle_gaps`` (the
        ``top`` longest, as [name, seconds], op times averaged over
        devices).
    """
    lo, hi = window_span(planes)
    devs = [_device_lines(p) for p in planes if _is_device(p)]
    devs = [d for d in devs if any(d.values())]
    if not devs:
        raise ValueError("the trace holds no device plane with operations")
    nd = len(devs)
    busy_total, op_time = 0.0, defaultdict(float)
    prog = {label: {"device_s": 0.0, "runs": 0.0} for label in programs}
    all_busy: List[Tuple[float, float]] = []
    for lines in devs:
        raw = lines.get(OPS_LINE) or [ev for evs in lines.values()
                                      for ev in evs]
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in raw
               if e > lo and s < hi]
        busy = union((s, e) for _, s, e in ops)
        busy_total += sum(e - s for s, e in busy)
        all_busy += busy
        for n, s, e in ops:
            op_time[n] += e - s
        for n, s, e in lines.get(MODULES_LINE, []):
            if e <= lo or s >= hi:
                continue
            for label, pats in programs.items():
                if any(p in n for p in pats):
                    prog[label]["device_s"] += (min(e, hi) - max(s, lo)) / 1e9
                    prog[label]["runs"] += 1.0 / nd
    edges = [lo] + [x for se in union(all_busy) for x in se] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    longest = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:top]
    names = _covering(planes, [(s + e) / 2 for s, e in longest])
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": nd,
        "busy_s": busy_total / nd / 1e9,
        "programs": prog,
        "device_ops": [[n[:NAME_CHARS], t / nd / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, (e - s) / 1e9]
                      for name, (s, e) in zip(names, longest)],
    }


def _covering(planes, times: List[float]) -> List[str]:
    """For each time, the innermost (shortest) host event that covers it,
    the window annotation aside."""
    best = [None] * len(times)
    for n, s, e in _host_events(planes):
        if n == WINDOW:
            continue
        for i, t in enumerate(times):
            if s <= t <= e and (best[i] is None or e - s < best[i][1]):
                best[i] = (n, e - s)
    return [b[0] if b else "no host span" for b in best]
