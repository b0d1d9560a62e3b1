"""The reduction of the program's host spans (``bench/lib/spans.py``) on
synthetic planes, and on the trace recorded on one TPU v5e before the
program had spans."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.lib import spans, trace  # noqa: E402

DATA = Path(__file__).with_name("data")
RECORDED = DATA / "serve_v5e.xplane.pb.gz"
MS = 1e6


def _ev(name, s, e, **meta):
    return NS(name=name, start_ns=s * MS, end_ns=e * MS,
              stats=list(meta.items()))


def _planes(host, other=()):
    """One host line (``host``) holding the 100 ms window, a second host
    line (``other``) and a device busy 10-20 ms and 70-75 ms."""
    return [
        NS(name="/host:CPU", lines=[
            NS(name="main", events=[_ev(trace.WINDOW, 0, 100)] + host),
            NS(name="worker", events=list(other))]),
        NS(name="/device:TPU:0", lines=[NS(name=trace.OPS_LINE, events=[
            _ev("fusion", 10, 20), _ev("copy", 70, 75)])])]


def _served():
    """Two offers, each a flush with its children; one ticket enqueued
    before the window (at -5 ms) and folded inside it."""
    return [
        _ev("repro.allocd.enqueue", -6, -5, seq=1),
        _ev("repro.wire.offer", 2, 6),
        _ev("repro.allocd.enqueue", 3, 4, seq=2),
        _ev("repro.allocd.fold", 8, 9, seq=1),
        _ev("repro.allocd.fold", 31, 32, seq=2),
        _ev("repro.wire.offer", 40, 44),
        _ev("repro.allocd.flush", 10, 30, tenant="a", events=1),
        _ev("repro.session.apply", 11, 14),
        _ev("repro.engine.solve", 14, 18),
        _ev("repro.allocd.device_wait", 18, 22),
        _ev("repro.wire.push", 22, 29),
        _ev("repro.allocd.flush", 50, 60, tenant="b", events=1),
        _ev("repro.allocd.device_wait", 52, 53),
        _ev("repro.wire.push", 53, 58),
        _ev("repro.allocd.flush", 95, 110, tenant="a", events=1),
        _ev("other runtime event", 60, 65),
    ]


def test_spans_nest_per_line():
    planes = _planes(_served(), [_ev("repro.wire.offer", 12, 13)])
    by = {(s.name, s.start): s for s in spans.collect(planes)}
    flush = by[("allocd.flush", 10 * MS)]
    assert [c.name for c in flush.children] == [
        "session.apply", "engine.solve", "allocd.device_wait", "wire.push"]
    assert flush.meta == {"tenant": "a", "events": 1}
    # another thread's span is no child, however it overlaps
    assert by[("wire.offer", 12 * MS)].parent is None
    assert by[("allocd.enqueue", 3 * MS)].parent.name == "wire.offer"
    # 20 ms less 3 + 4 + 4 + 7 covered by children
    assert flush.self_ns() == pytest.approx(2 * MS)


def test_served_readings():
    red = spans.reduce(_planes(_served()))
    assert red["count"]["allocd.flush"] == 3
    # folds in the window: seq 1 waited 8 - (-5), seq 2 31 - 4 ms
    assert sorted(red["queue_wait_s"]) == pytest.approx([0.013, 0.027])
    assert spans.queue_wait_ms(red) == pytest.approx(20.0)
    # flushes less their device waits and pushes: 9, 4 and 15 ms
    assert spans.flush_host_ms(red) == pytest.approx((9 + 4 + 15) / 3)
    assert spans.device_wait_ms(red) == pytest.approx((4 + 1) / 3)
    # (offers 4 + 4, pushes 7 + 5) over 2 offers
    assert spans.wire_host_ms(red) == pytest.approx(10.0)
    # top level: 2-6, 8-9, 10-30, 31-32, 40-44, 50-60, 95-100 (clipped)
    assert spans.host_busy_pct(red) == pytest.approx(45.0)
    assert spans.plan_stack_us(red) is None
    assert spans.plan_pull_us(red) is None


def test_plan_readings():
    host = [_ev("repro.plan.chunk", 0, 40, lanes=64),
            _ev("repro.plan.stack", 1, 30), _ev("repro.plan.pull", 31, 39),
            _ev("repro.plan.chunk", 40, 90, lanes=36),
            _ev("repro.plan.stack", 41, 80), _ev("repro.plan.pull", 81, 89),
            _ev("repro.plan.chunk", 120, 130, lanes=64)]
    red = spans.reduce(_planes(host))
    assert red["lanes"] == 100
    assert spans.plan_stack_us(red) == pytest.approx((29 + 39) * 1e3 / 100)
    assert spans.plan_pull_us(red) == pytest.approx(16 * 1e3 / 100)
    assert red["self_s"]["plan.chunk"] == pytest.approx(0.003 + 0.003)
    assert spans.host_busy_pct(red) == pytest.approx(90.0)
    assert spans.queue_wait_ms(red) is None


def test_no_spans_reads_nothing():
    """A trace of a program without spans, and events without stats."""
    red = spans.reduce(_planes([NS(name="PjitFunction(solve)",
                                   start_ns=0.0, end_ns=5 * MS)]))
    assert all(f(red) is None for f in spans.READINGS.values())
    assert all(f(None) is None for f in spans.READINGS.values())


def test_idle_gaps_name_the_open_span():
    gaps = spans.idle_gaps(_planes(_served()))
    by = {round(g["s"], 4): g for g in gaps}
    # 20-70 ms idle: at its middle (45 ms) no event is open
    assert by[0.05] == {"s": pytest.approx(0.05), "event": "no host span",
                        "span": None}
    # 0-10 ms: middle 5 ms inside the first offer (2-6 ms)
    assert by[0.01]["span"] == "wire.offer"
    # 75-100 ms: middle 87.5 ms, no span open
    assert by[0.025]["span"] is None


@pytest.fixture(scope="module")
def recorded():
    return spans.load(str(RECORDED))


def test_recorded_trace_without_spans(recorded):
    red = spans.reduce(recorded)
    assert red["count"] == {} and red["window_s"] > 0
    assert all(f(red) is None for f in spans.READINGS.values())
    assert all(g["span"] is None for g in spans.idle_gaps(recorded))


def test_recorded_trace_reduction_unchanged(recorded, tmp_path):
    """``trace.reduce``'s keys read as they did when the trace was
    recorded, whatever keys a later reduction adds."""
    want = json.loads((DATA / "serve_v5e.reduce.json").read_text())
    got = trace.reduce(recorded, {"solve": ["_solve_batch_jit"]})
    assert {k: got[k] for k in want} == want
    # the gzipped copy reads as the file trace.load reads
    path = tmp_path / "serve.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    assert trace.reduce(trace.load(str(path)),
                        {"solve": ["_solve_batch_jit"]}) == got
