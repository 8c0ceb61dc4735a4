"""From a profiler trace to device busy time, program times and idle gaps.

The trace is first brought into a plain form, `{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}`
(`load_xplane`), and every number is computed from that form
(`reduce_trace`), so the arithmetic can be checked against a small
recorded trace without a profiler.

- Busy time of a device is the UNION of the intervals of the events on
  its "XLA Ops" line. Overlapping events count once; the asynchronous
  copies of the "Async XLA Ops" line are not compute and are left out.
  Idle is the window less busy.
- A program's device time is the mean length of its events on the "XLA
  Modules" line, selected by the start of their name.
- An idle gap is named by what the host was doing: `inside_<span>` for
  the part of it that lies within a host span (the one that started
  last, where several enclose it), `before_<span>` for the part that
  precedes the next span to start, `after_last_span` for the rest. Host
  spans are the runtime's own `PjitFunction(<name>)` events, named
  `jit_<name>`, and `TraceAnnotation`s whose name starts with `pb:`.
- Exposed collective time of a device is the part of its collective
  events' union that no other event on the "XLA Ops" line covers.
"""

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)"
)
PJIT = re.compile(r"^PjitFunction\((.+)\)$")
ANNOTATION_PREFIX = "pb:"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> Dict:
    """The planes the reduction reads, in the plain form."""
    from jax.profiler import ProfileData

    planes = []
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        seen[plane.name] = [line.name for line in plane.lines]
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            on_device = plane.name != HOST_PLANE
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                [
                    op_name(e.name) if line.name == OPS_LINE else e.name,
                    float(e.start_ns), float(e.duration_ns),
                ]
                for e in line.events
                if on_device or host_span_name(e.name) is not None
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "lines_seen": seen}


def host_span_name(event_name: str) -> Optional[str]:
    match = PJIT.match(event_name)
    if match:
        return "jit_" + match.group(1)
    if event_name.startswith(ANNOTATION_PREFIX):
        return event_name[len(ANNOTATION_PREFIX):]
    return None


def op_name(event_name: str) -> str:
    """The instruction's name: the profiler labels an "XLA Ops" event
    with the whole HLO instruction, `%name = type op(operands...)`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same points."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def length(intervals: Iterable[Interval]) -> float:
    return sum(end - start for start, end in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of the disjoint sorted `a` not covered by the disjoint
    sorted `b`."""
    out = []
    j = 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def name_gaps(
    idle: Sequence[Interval], spans: Sequence[Tuple[str, float, float]]
) -> Dict[str, float]:
    """Seconds-agnostic: total idle length per name, in the intervals'
    own unit. `spans` are (name, start, end)."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    cuts = sorted({s[1] for s in spans} | {s[2] for s in spans})
    totals: Dict[str, float] = {}
    for lo, hi in idle:
        inner = cuts[bisect.bisect_right(cuts, lo):bisect.bisect_left(cuts, hi)]
        edges = [lo] + inner + [hi]
        for a, b in zip(edges, edges[1:]):
            if b <= a:
                continue
            mid = (a + b) / 2
            upto = bisect.bisect_right(starts, mid)
            enclosing = None
            for span in reversed(spans[:upto]):
                if span[2] > mid:
                    enclosing = span
                    break
            if enclosing is not None:
                name = "inside_" + enclosing[0]
            elif upto < len(spans):
                name = "before_" + spans[upto][0]
            else:
                name = "after_last_span"
            totals[name] = totals.get(name, 0.0) + (b - a)
    return totals


def _lines(trace: Dict, plane_name: str, line_name: str):
    for plane in trace["planes"]:
        if plane["name"] == plane_name:
            for line in plane["lines"]:
                if line["name"] == line_name:
                    yield line["events"]


def _top(totals: Dict[str, float], n: int = 10):
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]


def reduce_trace(trace: Dict, window_ns: Optional[Interval] = None) -> Dict:
    """Every number the harness takes from a trace, in seconds.

    `window_ns` bounds what is counted; by default it is the span from
    the first to the last event on any device's "XLA Ops" line, cut to
    the span the host's spans cover where there are any.
    """
    devices = sorted(
        plane["name"] for plane in trace["planes"]
        if DEVICE_PLANE.match(plane["name"])
    )
    ops = {
        d: [e for events in _lines(trace, d, OPS_LINE) for e in events]
        for d in devices
    }
    ops = {d: events for d, events in ops.items() if events}
    if not ops:
        raise ValueError(
            "the trace has no event on any device's XLA Ops line: no "
            "operation ran on the device in the traced window"
        )
    spans = [
        (host_span_name(name), start, start + duration)
        for plane in trace["planes"] if plane["name"] == HOST_PLANE
        for line in plane["lines"]
        for name, start, duration in line["events"]
        if host_span_name(name) is not None
    ]
    if window_ns is None:
        window_ns = (
            min(e[1] for events in ops.values() for e in events),
            max(e[1] + e[2] for events in ops.values() for e in events),
        )
        if spans:
            # The profiler stops the host's tracer before the device's:
            # count only where both were recording.
            window_ns = (
                max(window_ns[0], min(s[1] for s in spans)),
                min(window_ns[1], max(s[2] for s in spans)),
            )
    lo, hi = window_ns
    busy_ns, exposed_ns = [], []
    op_totals: Dict[str, float] = {}
    for device, events in ops.items():
        intervals = [(e[1], e[1] + e[2]) for e in events]
        busy = clip(union(intervals), lo, hi)
        busy_ns.append(length(busy))
        collective = clip(union(
            (e[1], e[1] + e[2]) for e in events if COLLECTIVE.match(e[0])
        ), lo, hi)
        compute = clip(union(
            (e[1], e[1] + e[2]) for e in events if not COLLECTIVE.match(e[0])
        ), lo, hi)
        exposed_ns.append(length(subtract(collective, compute)))
        for name, start, duration in events:
            inside = min(start + duration, hi) - max(start, lo)
            if inside > 0:
                op_totals[name] = op_totals.get(name, 0.0) + inside

    first = next(iter(ops))
    modules: Dict[str, Dict[str, float]] = {}
    for events in _lines(trace, first, MODULES_LINE):
        for name, start, duration in events:
            if start < lo or start + duration > hi:
                continue
            key = name.split("(")[0]
            entry = modules.setdefault(key, {"count": 0, "total_s": 0.0})
            entry["count"] += 1
            entry["total_s"] += duration / 1e9

    first_busy = clip(
        union((e[1], e[1] + e[2]) for e in ops[first]), lo, hi
    )
    named = name_gaps(subtract([(lo, hi)], first_busy), spans)

    chips = len(ops)
    return {
        "chips": chips,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / chips / 1e9,
        "collective_exposed_s": sum(exposed_ns) / chips / 1e9,
        "modules": modules,
        "device_ops": _top({k: v / chips / 1e9 for k, v in op_totals.items()}),
        "idle_gaps": _top({k: v / 1e9 for k, v in named.items()}),
        "lines_seen": trace.get("lines_seen", {}),
    }
