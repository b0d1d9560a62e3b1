"""The reduction from a profiler trace to device metrics: on synthetic
planes, and on a small trace recorded on one TPU v5e."""
from __future__ import annotations

import gzip
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench.lib import trace  # noqa: E402

RECORDED = Path(__file__).with_name("data") / "serve_v5e.xplane.pb.gz"
MS = 1e6


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, end_ns=e)
                            for n, s, e in evs]) for ln, evs in lines])


def _planes(window=True):
    host = [("dispatch", 20 * MS, 60 * MS)]
    if window:
        host.append((trace.WINDOW, 0.0, 100 * MS))
    return [
        _plane("/host:CPU", [("main", host)]),
        _plane("/device:TPU:0", [
            (trace.MODULES_LINE, [
                ("jit__solve_batch_jit(1)", 10 * MS, 20 * MS),
                ("jit__epoch_commit(2)", 70 * MS, 75 * MS),
                ("jit__solve_batch_jit(1)", 95 * MS, 110 * MS)]),
            (trace.OPS_LINE, [
                ("fusion.1", 10 * MS, 15 * MS), ("fusion.2", 12 * MS, 20 * MS),
                ("copy", 70 * MS, 75 * MS), ("fusion.1", 95 * MS, 110 * MS),
                ("early", -10 * MS, -5 * MS)])])]


def test_union_merges_overlaps():
    assert trace.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]


def test_reduce_synthetic():
    red = trace.reduce(_planes(), {"solve": ["_solve_batch_jit"]})
    assert red["window_s"] == pytest.approx(0.1)
    assert red["devices"] == 1
    # busy: 10-20, 70-75, 95-100 (clipped to the window)
    assert red["busy_s"] == pytest.approx(0.020)
    solve = red["programs"]["solve"]
    assert solve["device_s"] == pytest.approx(0.015)
    assert solve["runs"] == 2
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.010)]
    gaps = {round(s, 4): n for n, s in red["idle_gaps"]}
    # idle 0-10, 20-70 (the host dispatching at its middle), 75-95 ms
    assert gaps == {0.05: "dispatch", 0.02: "no host span",
                    0.01: "no host span"}


def test_reduce_two_devices():
    """Busy time is the mean over devices; a gap is idle on every device
    and is listed once."""
    planes = _planes() + [_plane("/device:TPU:1", [
        (trace.OPS_LINE, [("fusion.3", 20 * MS, 70 * MS)])])]
    red = trace.reduce(planes, {"solve": ["_solve_batch_jit"]})
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((0.020 + 0.050) / 2)
    assert red["programs"]["solve"]["runs"] == 1
    assert sorted(round(s, 4) for _, s in red["idle_gaps"]) == [0.01, 0.02]


def test_no_window_annotation_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(_planes(window=False), {})


def test_reduce_recorded_chip_trace(tmp_path):
    """A traced serve-32t-poisson run of 0.25 s (6 events) on one TPU v5e."""
    path = tmp_path / "serve.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    planes = trace.load(str(path))
    red = trace.reduce(planes, {"solve": ["_solve_batch_jit"]})
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    solve = red["programs"]["solve"]
    assert solve["runs"] >= 1 and solve["device_s"] > 0
    assert red["device_ops"] and red["idle_gaps"]
    assert all(isinstance(n, str) and s > 0 for n, s in red["idle_gaps"])
