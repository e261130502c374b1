"""Reduction of a profiler trace to the device's busy time, its idle share,
and a breakdown of device operations and idle gaps.

Two stages, so that a recorded trace can be checked in as a small fixture:

1. :func:`events_from_xplane` reads the profiler's ``.xplane.pb`` with
   ``jax.profiler.ProfileData`` and keeps the device operations (the ``XLA Ops``
   line of each device plane), the harness's own host spans (annotations
   whose names start with ``bench.``) and the program's spans (``hotswap.``,
   with the stats each carries), all on the profiler's clock in ns.
2. :func:`reduce_window` takes those events and a window and computes busy
   time as the union of operation intervals, the idle share, the operations
   that took most time, and the idle time by what the host was doing: the
   innermost span, the harness's or the program's, open at that moment.
"""
from __future__ import annotations

import bisect
import collections
import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "hotswap."
DEVICE_OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "no span"


def op_name(hlo: str, module: str = "") -> str:
    """A short, stable name for a device operation: the program's name (its
    module, without the fingerprint) and the HLO instruction's name, without
    the instruction's text (``%while.9 = (s32[], ...) while(...)``)."""
    op = hlo.split(" = ", 1)[0].strip().lstrip("%")
    mod = module.split("(", 1)[0]
    return f"{mod}/{op}" if mod else op


@dataclass
class TraceEvents:
    """Device operations per device, harness host spans and the program's
    spans, in ns."""
    device_ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)          # device plane -> [(op, start, end)]
    host_spans: List[Tuple[str, float, float]] = field(default_factory=list)
    # [(name, start, end, stats)] of the program's ``hotswap.`` spans
    program_spans: List[Tuple[str, float, float, Dict]] = field(
        default_factory=list)

    def to_json(self) -> str:
        return json.dumps({"device_ops": self.device_ops,
                           "host_spans": self.host_spans,
                           "program_spans": self.program_spans})

    @classmethod
    def from_json(cls, text: str) -> "TraceEvents":
        d = json.loads(text)
        return cls({k: [tuple(e) for e in v] for k, v in d["device_ops"].items()},
                   [tuple(e) for e in d["host_spans"]],
                   [tuple(e) for e in d.get("program_spans", [])])

    def spans(self, name: str) -> List[Tuple[str, float, float]]:
        """Host spans called ``name`` or ``name#<index>``."""
        return [s for s in self.host_spans
                if s[0] == name or s[0].startswith(name + "#")]


def events_from_xplane(path: str) -> TraceEvents:
    """Device operations, harness spans and program spans of one
    ``.xplane.pb`` file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = TraceEvents()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            ops = []
            for e in lines.get(DEVICE_OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= e.start_ns else ""
                ops.append((op_name(e.name, mod), e.start_ns,
                            e.start_ns + e.duration_ns))
            if ops:
                out.device_ops[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.host_spans.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        out.program_spans.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)))
    out.host_spans.sort(key=lambda s: s[1])
    out.program_spans.sort(key=lambda s: s[1])
    return out


def merge(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, sorted and disjoint."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint sorted ``merged`` covers."""
    starts = [a for a, _ in merged]
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < hi:
        a, b = merged[i]
        total += max(0.0, min(b, hi) - max(a, lo))
        i += 1
    return total


def _span_segments(spans: Sequence[Tuple[str, float, float]], lo: float,
                   hi: float) -> List[Tuple[float, float, str]]:
    """Partition ``[lo, hi]`` by the innermost open span (spans nest)."""
    edges = []
    for name, a, b in spans:
        label = name.split("#", 1)[0]
        edges.append((a, 1, -(b - a), label))
        edges.append((b, 0, 0, label))
    edges.sort()
    segs, stack, t = [], [], lo
    for when, is_start, _, label in edges:
        when = min(max(when, lo), hi)
        if when > t:
            segs.append((t, when, stack[-1] if stack else NO_SPAN))
            t = when
        if is_start:
            stack.append(label)
        elif label in stack:
            del stack[len(stack) - 1 - stack[::-1].index(label)]
    if t < hi:
        segs.append((t, hi, stack[-1] if stack else NO_SPAN))
    return segs


def reduce_window(ev: TraceEvents, lo: float, hi: float,
                  top: int = 10) -> Dict:
    """Busy and idle time of the devices in ``[lo, hi]`` (ns), averaged over
    the devices that ran any operation, with the ``breakdown`` lists."""
    if hi <= lo:
        raise ValueError("empty trace window")
    busy_by_dev = {}
    op_time: Dict[str, float] = collections.defaultdict(float)
    for dev, ops in ev.device_ops.items():
        merged = merge([(a, b) for _, a, b in ops], lo, hi)
        busy_by_dev[dev] = merged
        for name, a, b in ops:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_time[name] += d
    n_dev = max(len(busy_by_dev), 1)
    busy_ns = sum(sum(b - a for a, b in m) for m in busy_by_dev.values()) / n_dev
    idle_by_label: Dict[str, float] = collections.defaultdict(float)
    segs = _span_segments(
        ev.host_spans + [s[:3] for s in ev.program_spans], lo, hi)
    for merged in busy_by_dev.values():
        for a, b, label in segs:
            idle_by_label[label] += (b - a) - covered(merged, a, b)
    if not busy_by_dev:
        for a, b, label in segs:
            idle_by_label[label] += b - a
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_by_label.items(), key=lambda kv: -kv[1])[:top]
    window_ns = hi - lo
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "busy_intervals": busy_by_dev,
        "breakdown": {
            "device_ops": [[n, t / 1e9 / n_dev] for n, t in ops],
            "idle_gaps": [[n, t / 1e9 / n_dev] for n, t in gaps if t > 0],
        },
    }


def device_busy_in(reduced: Dict, lo: float, hi: float) -> float:
    """Device-busy ns inside ``[lo, hi]``, averaged over devices."""
    per_dev = reduced["busy_intervals"]
    if not per_dev:
        return 0.0
    return sum(covered(m, lo, hi) for m in per_dev.values()) / len(per_dev)


def window_of(ev: TraceEvents, name: str = "bench.window") -> Optional[Interval]:
    """Start and end of the harness's window span, if the trace holds it."""
    spans = ev.spans(name)
    return (spans[0][1], spans[0][2]) if spans else None
