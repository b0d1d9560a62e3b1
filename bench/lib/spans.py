"""Reduction of the program's own host spans in a profiler trace.

The program names its layers' host work with ``repro.<name>`` annotations
(``src/repro/spans.py``); a traced run records them beside the device's
operations, on one clock.  This module reads the ``repro.`` events of the
host planes, each with its metadata (the event's ``stats``; an event with
none has empty metadata), and nests them per host line: a span's children
are the ``repro.`` spans inside it on the same thread.

:func:`reduce` sums the spans that start inside the benchmark's window
annotation (:data:`bench.lib.trace.WINDOW`).  The functions after it turn
that reduction into the per-layer readings; each returns None where the
trace holds no span to read, as a program without spans gives.

    python -m bench.lib.spans TRACE.xplane.pb[.gz] ...

prints, per trace, the reduction, the readings and the window's longest
device-idle gaps, each with the innermost host event and the innermost
program span open at its middle.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench.lib import trace

PREFIX = "repro."


@dataclass
class Span:
    """One ``repro.`` event: ``name`` without the prefix, times in ns."""

    name: str
    start: float
    end: float
    meta: dict
    parent: Optional["Span"] = None
    children: List["Span"] = field(default_factory=list)

    @property
    def dur(self) -> float:
        return self.end - self.start

    def self_ns(self) -> float:
        """Duration less the part of it that the children cover."""
        return self.dur - sum(e - s for s, e in trace.union(
            (c.start, c.end) for c in self.children))


def collect(planes: Sequence) -> List[Span]:
    """Every ``repro.`` event of the host planes, nested per line."""
    out: List[Span] = []
    for plane in planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            evs = sorted(((ev.name, ev.start_ns, ev.end_ns,
                           dict(getattr(ev, "stats", None) or ()))
                          for ev in line.events
                          if ev.name.startswith(PREFIX)),
                         key=lambda e: (e[1], -e[2]))
            open_: List[Span] = []
            for name, s, e, meta in evs:
                sp = Span(name[len(PREFIX):], s, e, meta)
                while open_ and open_[-1].end < e:
                    open_.pop()
                if open_:
                    sp.parent = open_[-1]
                    open_[-1].children.append(sp)
                open_.append(sp)
                out.append(sp)
    return out


def queue_waits(spans: Sequence[Span], lo: float, hi: float) -> List[float]:
    """For each ticket folded in [lo, hi): its ``allocd.fold`` start less
    its ``allocd.enqueue`` end, paired by ``seq`` [ns]."""
    enq = {s.meta["seq"]: s.end for s in spans
           if s.name == "allocd.enqueue" and "seq" in s.meta}
    return [s.start - enq[s.meta["seq"]] for s in spans
            if s.name == "allocd.fold" and lo <= s.start < hi
            and s.meta.get("seq") in enq]


def reduce(planes: Sequence) -> dict:
    """The spans of the window, summed.

    Returns
    -------
    dict
        ``window_s``; per span name ``count``, ``total_s`` (durations) and
        ``self_s`` (less their children); ``queue_wait_s`` (the waits of
        :func:`queue_waits`); ``flush_host_s`` (per ``allocd.flush``, its
        duration less its ``allocd.device_wait`` and ``wire.push``
        children, summed); ``lanes`` (of the ``plan.chunk`` spans);
        ``busy_s`` (the union of the top-level spans, clipped to the
        window).
    """
    lo, hi = trace.window_span(planes)
    spans = collect(planes)
    inside = [s for s in spans if lo <= s.start < hi]
    count: Dict[str, int] = defaultdict(int)
    total: Dict[str, float] = defaultdict(float)
    self_: Dict[str, float] = defaultdict(float)
    flush_host = lanes = 0.0
    for s in inside:
        count[s.name] += 1
        total[s.name] += s.dur / 1e9
        self_[s.name] += s.self_ns() / 1e9
        if s.name == "allocd.flush":
            flush_host += (s.dur - sum(
                c.dur for c in s.children
                if c.name in ("allocd.device_wait", "wire.push"))) / 1e9
        elif s.name == "plan.chunk":
            lanes += s.meta.get("lanes", 0)
    busy = trace.union((max(s.start, lo), min(s.end, hi)) for s in inside
                       if s.parent is None)
    return {"window_s": (hi - lo) / 1e9, "count": dict(count),
            "total_s": dict(total), "self_s": dict(self_),
            "queue_wait_s": [w / 1e9 for w in queue_waits(spans, lo, hi)],
            "flush_host_s": flush_host, "lanes": lanes,
            "busy_s": sum(e - s for s, e in busy) / 1e9}


def _per(red: Optional[dict], names: Tuple[str, ...], over: str,
         scale: float) -> Optional[float]:
    """Summed durations of ``names`` per ``over`` span, times ``scale``."""
    n = (red or {}).get("count", {}).get(over, 0)
    if not n:
        return None
    return sum(red["total_s"].get(k, 0.0) for k in names) / n * scale


def queue_wait_ms(red: Optional[dict]) -> Optional[float]:
    """Median wait of a ticket in the daemon's queue [ms]."""
    waits = (red or {}).get("queue_wait_s")
    return statistics.median(waits) * 1e3 if waits else None


def flush_host_ms(red: Optional[dict]) -> Optional[float]:
    """Host time of one flush, less the wait for the device and the push
    of its report [ms]: epoch commit, solve dispatch, report assembly."""
    n = (red or {}).get("count", {}).get("allocd.flush", 0)
    return red["flush_host_s"] / n * 1e3 if n else None


def device_wait_ms(red: Optional[dict]) -> Optional[float]:
    """Time one flush waits for the device [ms]."""
    return _per(red, ("allocd.device_wait",), "allocd.flush", 1e3)


def wire_host_ms(red: Optional[dict]) -> Optional[float]:
    """Host time of the wire per offered event: its decode, submit and
    ticket frame, and the push of flush frames [ms]."""
    return _per(red, ("wire.offer", "wire.push"), "wire.offer", 1e3)


def host_busy_pct(red: Optional[dict]) -> Optional[float]:
    """Share of the window in which some program span was open [%]."""
    if not (red or {}).get("count") or red["window_s"] <= 0:
        return None
    return 100.0 * red["busy_s"] / red["window_s"]


def _per_lane(red: Optional[dict], name: str) -> Optional[float]:
    """Time of the planner's ``name`` span per candidate [us]."""
    if not (red or {}).get("lanes"):
        return None
    return red["total_s"].get(name, 0.0) / red["lanes"] * 1e6


def plan_stack_us(red: Optional[dict]) -> Optional[float]:
    """Stacking, padding and casting of a chunk, per candidate [us]."""
    return _per_lane(red, "plan.stack")


def plan_pull_us(red: Optional[dict]) -> Optional[float]:
    """Pull of a chunk's answers to the host, per candidate [us]."""
    return _per_lane(red, "plan.pull")


READINGS = {"queue_wait_ms": queue_wait_ms, "flush_host_ms": flush_host_ms,
            "device_wait_ms": device_wait_ms, "wire_host_ms": wire_host_ms,
            "host_busy_pct": host_busy_pct, "plan_stack_us": plan_stack_us,
            "plan_pull_us": plan_pull_us}


def idle_gaps(planes: Sequence, top: int = 10) -> List[dict]:
    """The window's ``top`` longest gaps in which no device was busy, each
    with the innermost host event and the innermost program span open at
    its middle (None where there is none)."""
    lo, hi = trace.window_span(planes)
    busy = []
    for p in planes:
        if p.name.startswith(trace.DEVICE_PREFIX):
            lines = {ln.name: [(ev.start_ns, ev.end_ns) for ev in ln.events]
                     for ln in p.lines}
            busy += lines.get(trace.OPS_LINE) or [
                iv for ivs in lines.values() for iv in ivs]
    edges = [lo] + [x for se in trace.union(
        (max(s, lo), min(e, hi)) for s, e in busy if e > lo and s < hi)
        for x in se] + [hi]
    gaps = sorted(((edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]),
                  key=lambda g: g[0] - g[1])[:top]
    mids = [(s + e) / 2 for s, e in gaps]
    events = trace._covering(planes, mids)
    spans = collect(planes)
    out = []
    for (s, e), t, ev in zip(gaps, mids, events):
        open_ = [sp for sp in spans if sp.start <= t <= sp.end]
        inner = min(open_, key=lambda sp: sp.dur) if open_ else None
        out.append({"s": (e - s) / 1e9, "event": ev,
                    "span": inner and inner.name})
    return out


def load(path: str) -> list:
    """The planes of a trace file, gzipped or not."""
    if not path.endswith(".gz"):
        return trace.load(path)
    import gzip
    from jax.profiler import ProfileData
    with gzip.open(path, "rb") as f:
        return list(ProfileData.from_serialized_xspace(f.read()).planes)


def main(paths: Sequence[str]) -> None:
    import json
    for path in paths:
        planes = load(path)
        red = reduce(planes)
        print(json.dumps({
            "trace": path,
            "readings": {k: f(red) for k, f in READINGS.items()},
            "spans": {k: red[k] for k in ("window_s", "count", "total_s",
                                          "self_s", "flush_host_s",
                                          "lanes", "busy_s")},
            "idle_gaps": idle_gaps(planes)}))


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
