"""Reduction of a profiler trace (``.xplane.pb``) to the program's own
layers: device time per jitted entry point and per model scope, and the
device's idle time put down to the innermost host span that covers it.

It extends :mod:`bench.trace` without changing what that module computes:
the window, the busy time, the waits and the idle share are
``trace.summarize``'s, read from the same file.

Device ops. Each event of a TPU plane's ``XLA Ops`` line points at event
metadata that carries the op's ``tf_op`` (its HLO ``op_name`` path, such as
``jit(decode_step)/while/body/decode_attention/dot_general``) and the
``program_id`` of its module; the ``XLA Modules`` line names each module
``jit_<entry>(<program_id>)``. An op's entry point is its module's entry,
and its scope the innermost (last) name of ``repro.models.scopes.SCOPES``
on its ``tf_op`` path; else ``unscoped`` for an op traced from the
program outside every scope, and ``compiler`` for one with no ``tf_op``,
which XLA inserted (layout copies, memory-space prefetches, async
slices). ``while``, ``conditional`` and ``call``
ops span their bodies, whose ops are events of their own, so op times are
over the other, leaf, ops. ``jax.profiler.ProfileData`` does not give
event metadata, so the file is decoded here with a minimal copy of the
public ``XSpace`` schema (tsl/profiler/protobuf/xplane.proto).

Host spans. The harness's spans start with ``bench.`` and the serving
path's with ``serve.`` (``repro.serving.spans``); they nest, and each idle
instant of the device goes to the innermost span covering it, or to
``bench.other`` where none does.

    python3 -m bench.layers <trace.xplane.pb>

prints the summary as JSON.
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Optional

from bench import trace

PREFIXES = ("bench.", "serve.")
OTHER = "bench.other"
UNSCOPED = "unscoped"
COMPILER = "compiler"
CONTROL = ("while", "conditional", "call")  # ops that span their bodies
PREFILL_ENTRIES = ("prefill", "suffix_prefill", "admit_finish", "slot_write")
TOP = trace.TOP
_MODULE = re.compile(r"^jit_(.+)\((\d+)\)$")


def _schema():
    """The message class of a minimal ``XSpace``: only the fields read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    I64, U64, DBL, STR, BYT, MSG = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE,
                                    F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE)
    REP = F.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="bench_xplane",
                                            syntax="proto3")

    def message(parent, name, fields):
        m = parent.add(name=name)
        for fname, num, ftype, *rest in fields:
            f = m.field.add(name=fname, number=num, type=ftype, label=F.LABEL_OPTIONAL)
            if rest:
                f.label = REP
                if rest[0]:
                    f.type_name = f".bench_xplane.{rest[0]}"
        return m

    def map_entry(parent, name, value):
        m = message(parent.nested_type, name, [("key", 1, I64), ("value", 2, MSG)])
        m.field[1].type_name = f".bench_xplane.{value}"
        m.options.map_entry = True

    message(fd.message_type, "XSpace", [("planes", 1, MSG, "XPlane")])
    plane = message(fd.message_type, "XPlane", [
        ("name", 2, STR), ("lines", 3, MSG, "XLine"),
        ("event_metadata", 4, MSG, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, MSG, "XPlane.StatMetadataEntry")])
    map_entry(plane, "EventMetadataEntry", "XEventMetadata")
    map_entry(plane, "StatMetadataEntry", "XStatMetadata")
    message(fd.message_type, "XLine", [("name", 2, STR), ("timestamp_ns", 3, I64),
                                       ("events", 4, MSG, "XEvent")])
    message(fd.message_type, "XEvent", [("metadata_id", 1, I64), ("offset_ps", 2, I64),
                                        ("duration_ps", 3, I64), ("stats", 4, MSG, "XStat")])
    stat = message(fd.message_type, "XStat", [
        ("metadata_id", 1, I64), ("double_value", 2, DBL), ("uint64_value", 3, U64),
        ("int64_value", 4, I64), ("str_value", 5, STR), ("bytes_value", 6, BYT),
        ("ref_value", 7, U64)])
    stat.oneof_decl.add(name="value")
    for f in stat.field[1:]:
        f.oneof_index = 0
    message(fd.message_type, "XEventMetadata", [("name", 2, STR), ("stats", 5, MSG, "XStat")])
    message(fd.message_type, "XStatMetadata", [("name", 2, STR)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stats(stats, names) -> dict:
    """{stat name: value} of a repeated ``XStat``; a ``ref_value`` names a
    string kept in the plane's stat metadata."""
    out = {}
    for s in stats:
        kind = s.WhichOneof("value")
        if kind is None:
            continue
        v = getattr(s, kind)
        out[names.get(s.metadata_id, "")] = names.get(v, v) if kind == "ref_value" else v
    return out


def scope_of(tf_op: str, scopes) -> str:
    """The innermost of ``scopes`` on an op's ``tf_op`` path, else
    ``unscoped``; ``compiler`` for an op with no path."""
    if not tf_op:
        return COMPILER
    for part in reversed(tf_op.split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def is_leaf(op: str) -> bool:
    """False for ops named as control flow (``while.35``), whose events
    span the events of their bodies."""
    return op.split(".", 1)[0] not in CONTROL


@dataclass
class Events:
    """Intervals in ns on the profiler's clock (the same as
    ``ProfileData``'s: start truncated to the ns, duration floored)."""

    base: trace.Events  # what bench/trace.py reads from the same file
    ops: dict = field(default_factory=dict)  # device -> [(entry, scope, op, start, end)]
    modules: dict = field(default_factory=dict)  # device -> [(entry, start, end)]
    spans: list = field(default_factory=list)  # [(name, start, end, args)]


def _interval(line, e) -> tuple[float, float]:
    start = float((line.timestamp_ns * 1000 + e.offset_ps) // 1000)
    return start, start + float(e.duration_ps // 1000)


def read(path: str, scopes=None) -> Events:
    if scopes is None:
        from repro.models.scopes import SCOPES as scopes
    with open(path, "rb") as f:
        space = _schema().FromString(f.read())
    ev = Events(base=trace.read(path))
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:"):
            entries: dict = {}
            mods = lines.get("XLA Modules")
            if mods is not None:
                ev.modules[plane.name] = []
                for e in mods.events:
                    m = _MODULE.match(meta[e.metadata_id].name)
                    entry = m.group(1) if m else meta[e.metadata_id].name
                    if m:
                        entries[int(m.group(2))] = entry
                    ev.modules[plane.name].append((entry, *_interval(mods, e)))
            ops = lines.get("XLA Ops")
            if ops is not None:
                out = ev.ops[plane.name] = []
                named: dict = {}  # metadata id -> (entry, scope, op)
                for e in ops.events:
                    if e.metadata_id not in named:
                        md = meta[e.metadata_id]
                        st = _stats(md.stats, names)
                        named[e.metadata_id] = (
                            entries.get(st.get("program_id"), "unknown"),
                            scope_of(str(st.get("tf_op", "")), scopes),
                            trace.op_name(md.name))
                    out.append((*named[e.metadata_id], *_interval(ops, e)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if name.startswith(PREFIXES):
                        ev.spans.append((name, *_interval(line, e),
                                         _stats(e.stats, names)))
    return ev


def innermost(spans: list, lo: float, hi: float) -> list:
    """[(start, end, name)] cutting [lo, hi) by the innermost span active
    at each instant (the latest started of those covering it, of two that
    start together the one that ends first); instants that no span covers
    are left out."""
    points = sorted({lo, hi} | {t for _, s, e, _ in spans for t in (s, e) if lo < t < hi})
    by_start = sorted(spans, key=lambda sp: sp[1])
    out: list = []
    active: list = []
    j = 0
    for a, b in zip(points, points[1:]):
        while j < len(by_start) and by_start[j][1] <= a:
            active.append(by_start[j])
            j += 1
        active = [sp for sp in active if sp[2] > a]
        if active:
            name = max(active, key=lambda sp: (sp[1], -sp[2]))[0]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1][1] = b
            else:
                out.append([a, b, name])
    return out


def _attribute(gaps: list, segments: list, into: dict) -> None:
    """Add each gap's time to the segment names covering it, the rest to
    ``bench.other``. Both lists are sorted and disjoint."""
    j = 0
    for g0, g1 in gaps:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= g0:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < g1:
            ov = trace._overlap(segments[k][0], segments[k][1], g0, g1)
            into[segments[k][2]] += ov
            covered += ov
            k += 1
        if g1 - g0 > covered:
            into[OTHER] += g1 - g0 - covered


@dataclass
class Summary:
    base: trace.Summary  # window, busy, waits, idle share: bench/trace.py's
    entry_s: dict  # entry -> seconds of its module executions that started in the window
    entry_runs: dict  # entry -> those executions
    scope_s: dict  # (entry, scope) -> leaf-op seconds in the window
    op_s: dict  # "<entry>/<scope>/<op>" -> leaf-op seconds in the window
    idle_gaps: list  # [[innermost host span, seconds]]
    spans: list  # [(name, start_s, end_s, args)] host spans that started in the window

    @property
    def idle_pct(self) -> Optional[float]:
        return self.base.idle_pct

    @property
    def device_ops(self) -> list:
        """[[op, seconds]] of the leaf ops that took most time."""
        return [[k, v] for k, v in sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]]


def summarize(ev: Events) -> Optional[Summary]:
    """None where ``trace.summarize`` gives None. Times per device are
    means over the devices."""
    base = trace.summarize(ev.base)
    if base is None:
        return None
    lo, hi = next((s, e) for n, s, e in ev.base.spans if n == trace.WINDOW)
    spans = [sp for sp in ev.spans if sp[2] > lo and sp[1] < hi]
    segments = innermost([sp for sp in spans if sp[0] != trace.WINDOW], lo, hi)
    entry_s: dict = defaultdict(float)
    entry_runs: dict = defaultdict(int)
    scope_s: dict = defaultdict(float)
    per_op: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    devices = [d for d, ops in ev.base.ops.items() if ops]
    n = len(devices)
    for dev in devices:
        for entry, s, e in ev.modules.get(dev, ()):
            if lo <= s < hi:
                entry_s[entry] += e - s
                entry_runs[entry] += 1
        for entry, scope, op, s, e in ev.ops.get(dev, ()):
            if is_leaf(op):
                t = trace._overlap(s, e, lo, hi)
                scope_s[(entry, scope)] += t
                per_op[f"{entry}/{scope}/{op}"] += t
        busy = trace.union([(s, e) for _, s, e in ev.base.ops[dev]], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        _attribute([(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a], segments, gaps)
    ns = 1e-9
    return Summary(
        base=base,
        entry_s={k: v / n * ns for k, v in entry_s.items()},
        entry_runs={k: v // n for k, v in entry_runs.items()},
        scope_s={k: v / n * ns for k, v in scope_s.items()},
        op_s={k: v / n * ns for k, v in per_op.items()},
        idle_gaps=[[k, v / n * ns] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])],
        spans=[(name, s * ns, e * ns, args) for name, s, e, args in spans if s >= lo],
    )


# --- the per-layer numbers these times give -------------------------------------


def decode_device_ms(s: Summary, steps_per_admit: int) -> Optional[float]:
    """Device time of one decode sub-step: the ``decode_step`` module's
    time over its executions times the sub-steps each fuses, in ms."""
    runs = s.entry_runs.get("decode_step", 0)
    if not runs:
        return None
    return s.entry_s["decode_step"] / (runs * steps_per_admit) * 1e3


def scope_pct(s: Summary, entry: str, scope: str) -> Optional[float]:
    """Share of an entry point's leaf-op time under one scope, in %."""
    total = sum(t for (e, _), t in s.scope_s.items() if e == entry)
    if total <= 0:
        return None
    return 100.0 * s.scope_s.get((entry, scope), 0.0) / total


def decode_attn_pct(s: Summary) -> Optional[float]:
    return scope_pct(s, "decode_step", "decode_attention")


def prefill_ms_per_ktok(s: Summary, prefill_tokens: int) -> Optional[float]:
    """Device time of the admission entry points per thousand real prompt
    tokens admitted (``prefill_tokens``: the counter's change over the
    window), in ms."""
    if prefill_tokens <= 0:
        return None
    return sum(s.entry_s.get(e, 0.0) for e in PREFILL_ENTRIES) / (prefill_tokens / 1000) * 1e3


def admit_pad_pct(stats0: dict, stats1: dict) -> Optional[float]:
    """Share of the prefilled rows x bucketed lengths that were padding,
    from the scheduler's counters at the window's start and end, in %."""
    padded = stats1["prefill_padded_tokens"] - stats0["prefill_padded_tokens"]
    real = stats1["prefill_tokens"] - stats0["prefill_tokens"]
    return 100.0 * (padded - real) / padded if padded > 0 else None


def step_host_ms(s: Summary) -> Optional[float]:
    """Mean time of the window's ``serve.step`` spans outside their
    ``serve.*.sync`` children (the host's own work per step), in ms."""
    steps = [(a, b) for name, a, b, _ in s.spans if name == "serve.step"]
    if not steps:
        return None
    syncs = sorted((a, b) for name, a, b, _ in s.spans
                   if name.startswith("serve.") and name.endswith(".sync"))
    host = 0.0
    for a, b in steps:
        host += (b - a) - sum(trace._overlap(c, d, a, b) for c, d in syncs)
    return host / len(steps) * 1e3


def as_dict(s: Summary) -> dict:
    """The summary as plain JSON-able data."""
    b = s.base
    return {
        "window_s": b.window_s, "busy_s": b.busy_s, "waiting_s": b.waiting_s,
        "idle_pct": b.idle_pct, "n_devices": b.n_devices,
        "entry_s": s.entry_s, "entry_runs": s.entry_runs,
        "scope_s": [[e, sc, t] for (e, sc), t in sorted(s.scope_s.items(), key=lambda kv: -kv[1])],
        "device_ops": s.device_ops, "idle_gaps": s.idle_gaps,
        "decode_attn_pct": decode_attn_pct(s), "step_host_ms": step_host_ms(s),
    }


if __name__ == "__main__":
    summary = summarize(read(sys.argv[1]))
    print(json.dumps(None if summary is None else as_dict(summary), indent=1))
