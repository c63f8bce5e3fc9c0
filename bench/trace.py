"""Reduction of a profiler trace (``.xplane.pb``) to the device's busy time,
its idle gaps labelled by what the host was doing, and the operations that
took the most time.

Device time: the events of each TPU plane's ``XLA Ops`` line (``XLA
Modules`` where a plane has no op line). Busy is the union of their
intervals; idle is the rest of the traced window.

Host spans: the harness wraps each ``step()`` and each phase of its own
work in a ``jax.profiler.TraceAnnotation`` whose name starts with
``bench.``; they land on the host plane on the same clock as the device
events. ``bench.window`` spans the traced window; ``bench.wait`` marks the
harness waiting for an arrival while nothing is resident or queued, which
the idle share leaves out.
"""
from __future__ import annotations

from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Optional

PREFIX = "bench."
WINDOW = "bench.window"
WAIT = "bench.wait"
TOP = 10


@dataclass
class Events:
    """Intervals in ns: device ops per device, host spans by name."""

    ops: dict = field(default_factory=dict)  # device -> [(name, start, end)]
    spans: list = field(default_factory=list)  # [(name, start, end)]


def read(path: str) -> Events:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    ev = Events()
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is not None:
                ev.ops[plane.name] = [
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        ev.spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return ev


def op_name(name: str) -> str:
    """An op event is named by its whole HLO instruction
    (``%while.35 = (s32[], bf16[...]) while(...)``); keep its name."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _intersection(a: list, b: list) -> float:
    """Length of the overlap of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += _overlap(a[i][0], a[i][1], b[j][0], b[j][1])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _split(host: list, starts: list, g0: float, g1: float, into: dict) -> None:
    """Add the idle gap [g0, g1) to ``into``, split over the host spans
    that cover it (the harness's spans follow one another on its thread);
    time that no span covers goes to ``bench.other``."""
    covered = 0.0
    i = max(0, bisect_right(starts, g0) - 1)
    while i < len(host) and host[i][0] < g1:
        ov = _overlap(host[i][0], host[i][1], g0, g1)
        if ov > 0:
            into[host[i][2]] += ov
            covered += ov
        i += 1
    if g1 - g0 > covered:
        into["bench.other"] += g1 - g0 - covered


@dataclass
class Summary:
    window_s: float  # the traced window
    busy_s: float  # mean over devices of the union of device-op time
    waiting_s: float  # host waiting on an arrival with nothing to do
    busy_waiting_s: float  # the part of busy_s that fell in those waits
    device_ops: list  # [[name, seconds]] most time first
    idle_gaps: list  # [[host span, seconds]] idle time by what the host did
    n_devices: int

    @property
    def idle_pct(self) -> Optional[float]:
        """Idle share of the window outside the waits for arrivals: the
        device's busy time and the span it is over both leave them out."""
        active = self.window_s - self.waiting_s
        if active <= 0:
            return None
        return 100.0 * (1.0 - (self.busy_s - self.busy_waiting_s) / active)


def summarize(ev: Events) -> Optional[Summary]:
    """None when the trace holds no window span or no device operation."""
    windows = [(s, e) for n, s, e in ev.spans if n == WINDOW]
    if not windows or not any(ev.ops.values()):
        return None
    lo, hi = windows[0]
    host = sorted((s, e, n) for n, s, e in ev.spans if n != WINDOW and e > lo and s < hi)
    starts = [s for s, _, _ in host]
    waits = union([(s, e) for s, e, n in host if n == WAIT], lo, hi)
    waiting = sum(e - s for s, e in waits)

    per_op: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    busy_total = busy_waiting = 0.0
    devices = [d for d, ops in ev.ops.items() if ops]
    for dev in devices:
        ops = ev.ops[dev]
        for name, s, e in ops:
            per_op[name] += _overlap(s, e, lo, hi)
        busy = union([(s, e) for _, s, e in ops], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        busy_waiting += _intersection(busy, waits)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            _split(host, starts, g0, g1, gaps)
    ns = 1e-9
    top = lambda d: [[k, v * ns] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return Summary(
        window_s=(hi - lo) * ns,
        busy_s=busy_total / len(devices) * ns,
        waiting_s=waiting * ns,
        busy_waiting_s=busy_waiting / len(devices) * ns,
        device_ops=top(per_op),
        idle_gaps=[[k, v / len(devices)] for k, v in top(gaps)],
        n_devices=len(devices),
    )
