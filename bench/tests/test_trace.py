"""The trace reduction: busy union, idle share, labelled gaps, op totals."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

MS = 1_000_000  # ns


def synthetic():
    ev = trace.Events()
    ev.spans = [
        ("bench.window", 0, 100 * MS),
        ("bench.decode_step", 0, 30 * MS),
        ("bench.bookkeeping", 30 * MS, 40 * MS),
        ("bench.wait", 40 * MS, 60 * MS),
        ("bench.admit_step", 60 * MS, 100 * MS),
    ]
    ev.ops["/device:TPU:0"] = [
        ("fusion.1", 2 * MS, 12 * MS), ("fusion.2", 10 * MS, 28 * MS),  # overlap
        ("dot.3", 62 * MS, 90 * MS), ("fusion.1", 95 * MS, 120 * MS),  # clipped
    ]
    return ev


def test_union_merges_and_clips():
    assert trace.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [[1, 4], [5, 10]]


def test_synthetic_summary():
    s = trace.summarize(synthetic())
    busy = (28 - 2) + (90 - 62) + (100 - 95)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(busy / 1000)
    assert s.waiting_s == pytest.approx(0.02)
    assert s.idle_pct == pytest.approx(100 * (1 - busy / 80))
    gaps = dict(s.idle_gaps)
    assert gaps["bench.decode_step"] == pytest.approx((2 + 2) / 1000)
    assert gaps["bench.bookkeeping"] == pytest.approx(10 / 1000)
    assert gaps["bench.wait"] == pytest.approx(20 / 1000)
    assert gaps["bench.admit_step"] == pytest.approx((2 + 5) / 1000)
    ops = dict(s.device_ops)
    assert ops["fusion.1"] == pytest.approx((10 + 5) / 1000)
    assert s.device_ops[0][0] == "dot.3"


def test_device_work_during_a_wait_is_left_out_of_busy():
    ev = synthetic()
    ev.ops["/device:TPU:0"].append(("copy.4", 45 * MS, 50 * MS))  # inside the wait
    s = trace.summarize(ev)
    busy = (28 - 2) + (90 - 62) + (100 - 95)
    assert s.busy_s == pytest.approx((busy + 5) / 1000)
    assert s.busy_waiting_s == pytest.approx(5 / 1000)
    assert s.idle_pct == pytest.approx(100 * (1 - busy / 80))


def test_intersection_of_interval_lists():
    a = [[0, 4], [6, 10], [12, 20]]
    b = [[2, 7], [9, 13], [19, 30]]
    assert trace._intersection(a, b) == (2 + 1) + (1 + 1) + 1


def test_no_window_or_no_device_gives_nothing():
    ev = synthetic()
    assert trace.summarize(trace.Events(ops=ev.ops)) is None
    assert trace.summarize(trace.Events(spans=ev.spans)) is None


RECORDED = Path(__file__).parent / "data" / "small.xplane.pb"


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e by ``record_trace.py``: six steps of a
    small jitted program between the harness's host spans."""
    from jax.profiler import ProfileData

    s = trace.summarize(trace.read(str(RECORDED)))
    prof = ProfileData.from_file(str(RECORDED))
    host = [e for p in prof.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    (win,) = [e for e in host if e.name == "bench.window"]
    lo, hi = win.start_ns, win.start_ns + win.duration_ns
    # busy: the union of the device's op intervals, computed here on a
    # nanosecond grid of the window
    (dev,) = [p for p in prof.planes if p.name == "/device:TPU:0"]
    (ops,) = [ln for ln in dev.lines if ln.name == "XLA Ops"]
    grid = np.zeros(int(hi - lo), bool)
    for e in ops.events:
        a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
        if b > a:
            grid[int(a - lo): int(b - lo)] = True
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert s.busy_s == pytest.approx(grid.sum() * 1e-9, rel=1e-3)
    assert 0 < s.busy_s < s.window_s
    (wait,) = [e for e in host if e.name == "bench.wait"]
    assert s.waiting_s == pytest.approx(wait.duration_ns * 1e-9)
    # every idle nanosecond lands in exactly one label
    gaps = dict(s.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert gaps["bench.wait"] == pytest.approx(s.waiting_s, rel=0.01)
    assert {"bench.decode_step", "bench.admit_step", "bench.bookkeeping"} <= set(gaps)
    assert s.idle_pct == pytest.approx(
        100 * (1 - (s.busy_s - s.busy_waiting_s) / (s.window_s - s.waiting_s)))
    # op names are the HLO instruction names, not their whole text
    assert all(" = " not in name for name, _ in s.device_ops)
    assert sum(t for _, t in s.device_ops) >= s.busy_s
