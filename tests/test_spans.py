"""The program's host spans (``repro.spans``), recorded by a real profiler
session on the CPU and read back with the benchmark's span reduction
(``bench/lib/spans.py``): a small wire-served run and a two-chunk plan."""
from __future__ import annotations

import asyncio
import math
import re
import sys
from pathlib import Path

import jax
import pytest

from repro.core import (AdmissionWindow, CapacityEngine, FlushPolicy,
                        Policies, RoundingPolicy, SolverConfig,
                        sample_event_trace, sample_scenario)
from repro.core.planning import PlanSpec, VMTier, generate_grid, solve_plan
from repro.serving.allocd import RECENT_EVENTS, AllocDaemon
from repro.serving.client import AllocClient
from repro.serving.server import AllocServer
from repro.spans import PREFIX, SPANS

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench.lib import spans as bspans  # noqa: E402
from bench.lib import trace  # noqa: E402

SERVED = ("wire.offer", "wire.push", "allocd.enqueue", "allocd.fold",
          "allocd.flush", "allocd.device_wait", "session.apply",
          "engine.solve")
PLANNED = ("plan.chunk", "plan.stack", "plan.pull")
TENANTS, LANES, CLASSES, EVENTS = ("t0", "t1"), 2, 3, 4


def _record(tmp: Path, work) -> list:
    """The planes of a profiler trace of ``work()`` inside the benchmark's
    window annotation."""
    trace.start(tmp)
    window = trace.open_window()
    try:
        work()
    finally:
        trace.stop(window)
    return trace.load(trace.newest_trace(str(tmp)))


def _lanes(seed):
    key = jax.random.PRNGKey(seed)
    return [sample_scenario(jax.random.fold_in(key, b), CLASSES,
                            capacity_factor=1.3) for b in range(LANES)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two wire tenants on loopback, one flush per event."""
    lanes = {nm: _lanes(i) for i, nm in enumerate(TENANTS)}
    events = {nm: sample_event_trace(
        10 + i, AdmissionWindow(lanes[nm], n_max=2 * CLASSES), EVENTS)
        for i, nm in enumerate(TENANTS)}
    engine = CapacityEngine(SolverConfig(), Policies(
        flush=FlushPolicy(max_events=1),
        rounding=RoundingPolicy(enabled=False)))

    async def run():
        server = AllocServer(AllocDaemon(engine, queue_limit=64))
        await server.start()
        client = await AllocClient.connect(*server.address)
        for nm in TENANTS:
            await client.register_tenant(nm, lanes[nm], n_max=2 * CLASSES)
        tickets = [client.offer(nm, ev) for nm in TENANTS
                   for ev in events[nm]]
        for tk in tickets:
            assert await tk.result() is not None
        await client.close()
        await server.close()
        return server.daemon

    out = {}

    def work():
        out["daemon"] = asyncio.run(run())

    planes = _record(tmp_path_factory.mktemp("served"), work)
    return planes, bspans.collect(planes), out["daemon"]


def test_served_path_records_every_served_span(served):
    _, spans, _ = served
    names = {s.name for s in spans}
    assert set(SERVED) <= names
    assert not names & set(PLANNED)
    assert sum(s.name == "wire.offer" for s in spans) == len(TENANTS) * EVENTS


def test_fold_pairs_enqueue_by_seq(served):
    _, spans, _ = served
    enq = {s.meta["seq"]: s for s in spans if s.name == "allocd.enqueue"}
    fold = {s.meta["seq"]: s for s in spans if s.name == "allocd.fold"}
    assert len(enq) == len(fold) == len(TENANTS) * EVENTS
    assert enq.keys() == fold.keys()
    for seq, f in fold.items():
        assert f.start >= enq[seq].end
        assert enq[seq].parent.name == "wire.offer"


def _ancestors(s):
    while s.parent is not None:
        s = s.parent
        yield s.name


def test_flush_children_nest_inside_flush(served):
    _, spans, daemon = served
    flushes = [s for s in spans if s.name == "allocd.flush"]
    assert sum(s.meta["events"] for s in flushes) == len(TENANTS) * EVENTS
    assert {s.meta["tenant"] for s in flushes} == set(TENANTS)
    for name in ("session.apply", "engine.solve", "allocd.device_wait",
                 "wire.push"):
        assert all("allocd.flush" in _ancestors(s) for s in spans
                   if s.name == name), name
    for f in flushes:
        kids = [c.name for c in f.children]
        assert kids.count("allocd.device_wait") == 1
        assert kids.count("wire.push") == 1
        # the daemon answers only after the device is done
        wait = next(c for c in f.children if c.name == "allocd.device_wait")
        push = next(c for c in f.children if c.name == "wire.push")
        assert wait.end <= push.start
    assert len(daemon.latencies_s) == len(TENANTS) * EVENTS


def test_served_readings(served):
    planes, _, _ = served
    red = bspans.reduce(planes)
    got = {k: f(red) for k, f in bspans.READINGS.items()}
    assert got["queue_wait_ms"] is not None
    assert math.isfinite(got["queue_wait_ms"]) and got["queue_wait_ms"] >= 0
    for k in ("flush_host_ms", "device_wait_ms", "wire_host_ms"):
        assert math.isfinite(got[k]) and got[k] > 0, k
    assert 0 < got["host_busy_pct"] <= 100
    assert got["plan_stack_us"] is None and got["plan_pull_us"] is None


SPEC = PlanSpec(
    n_classes=3, profile="poisson", rate=40.0, trace_events=64,
    cluster_sizes=(900.0, 4000.0),
    vm_tiers=(VMTier("small", 1.0, 6.0), VMTier("big", 2.0, 10.0)),
    deadline_scales=(0.9, 1.0), seed=3)


@pytest.mark.parametrize("warm_start", [False, True])
def test_plan_chunks_count_their_lanes(tmp_path, warm_start):
    """Two chunks (cold: 4 + 4 candidates; warm: 4 chains at each of 2
    deadlines): every candidate is counted once by ``lanes``."""
    out = {}

    def work():
        plan = SPEC if warm_start else generate_grid(SPEC)
        out["report"] = solve_plan(plan, chunk=4, warm_start=warm_start)

    planes = _record(tmp_path, work)
    spans = bspans.collect(planes)
    chunks = [s for s in spans if s.name == "plan.chunk"]
    assert len(chunks) == out["report"].n_chunks == 2
    assert sum(c.meta["lanes"] for c in chunks) == SPEC.n_candidates
    for c in chunks:
        assert sorted(k.name for k in c.children) == ["plan.pull",
                                                      "plan.stack"]
        # a uniform grid: no lane is class-padded on the host
        stack = next(k for k in c.children if k.name == "plan.stack")
        assert stack.meta["ragged"] == 0
    red = bspans.reduce(planes)
    assert red["lanes"] == SPEC.n_candidates
    for f in (bspans.plan_stack_us, bspans.plan_pull_us):
        assert math.isfinite(f(red)) and f(red) > 0
    assert bspans.queue_wait_ms(red) is None
    assert bspans.flush_host_ms(red) is None


def test_daemon_logs_keep_the_most_recent_events():
    daemon = AllocDaemon(CapacityEngine())
    for log in (daemon.latencies_s, daemon.fold_log, daemon.flush_log):
        assert log.maxlen == RECENT_EVENTS


def _section(text, number):
    return text.split(f"\n## {number}.")[1].split("\n## ")[0]


def test_spans_match_the_layer_map():
    """Every span is listed with its layer in PERF.md section 3."""
    rows = re.findall(r"^\| `(repro\.[\w.]+)` \| ([\w ]+?) \|",
                      _section((ROOT / "PERF.md").read_text(), 3), re.M)
    assert sorted(rows) == sorted((PREFIX + n, layer) for n, layer in SPANS)


def test_every_span_is_opened_and_documented():
    """The program opens each listed span, no other, and the operations
    guide tells an operator what each one says."""
    used = re.findall(r'\bspan\("([\w.]+)"',
                      "".join(p.read_text() for p in
                              (ROOT / "src" / "repro").rglob("*.py")))
    names = [n for n, _ in SPANS]
    assert sorted(set(used)) == sorted(names)
    ops = (ROOT / "docs" / "OPERATIONS.md").read_text()
    for n in names:
        assert f"`{PREFIX}{n}`" in ops, n
