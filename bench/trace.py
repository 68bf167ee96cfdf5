"""Reduce a profiler trace (``.xplane.pb``) to intervals.

``jax.profiler.ProfileData`` reads the file.  Each device is a plane
named ``/device:TPU:<id>``; its ``XLA Ops`` line holds one event per
operation the device ran, named by the operation's HLO text, of which
only the instruction's name is kept (``wave_block_shots_pallas.14``).
A ``while`` loop is itself an event that spans the operations of its
body.  The host plane ``/host:CPU`` holds the spans
the benchmark opened with ``jax.profiler.TraceAnnotation``, named
``bench.<what>``.  All times here are nanoseconds on the trace's clock.

Interval sets are sorted lists of disjoint ``(start, end)`` pairs.
"""
from __future__ import annotations

import bisect
import dataclasses
import math
import re
from collections import defaultdict

from jax.profiler import ProfileData

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
#: operations that move data between chips, by instruction name
COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|all-to-all"
    r"|reduce-scatter|send|recv)")


@dataclasses.dataclass
class Trace:
    #: device id -> [(start, end, op name)] in start order
    ops: dict[int, list[tuple[float, float, str]]]
    #: [(start, end, span name)] of the benchmark's host spans
    spans: list[tuple[float, float, str]]

    def span(self, name: str) -> list[tuple[float, float]]:
        """Every (start, end) of the host spans called ``name``."""
        return [(a, b) for a, b, n in self.spans if n == name]

    def busy(self, device: int, match=None) -> list[tuple[float, float]]:
        """Union of the device's operations, or of those whose name
        ``match`` (a compiled pattern) finds."""
        return union((a, b) for a, b, n in self.ops.get(device, ())
                     if match is None or match.search(n))

    def op_seconds(self, match, devices=None) -> float:
        """Summed duration of the matching operations, in seconds."""
        return sum(b - a for d, evs in self.ops.items()
                   if devices is None or d in devices
                   for a, b, n in evs if match.search(n)) / 1e9


def load(path) -> Trace:
    data = ProfileData.from_file(str(path))
    ops: dict[int, list] = {}
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.start_ns, e.end_ns, short_name(e.name))
                               for e in line.events)
            ops[int(m.group(1))] = sorted(evs)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, spans=sorted(spans))


def short_name(hlo: str) -> str:
    """``%fusion.31 = f32[...] fusion(...)`` -> ``fusion.31``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def category(name: str) -> str:
    """An instruction's name without its numbering: ``fusion.31`` and
    ``broadcast.287.clone`` -> ``fusion`` and ``broadcast``."""
    return re.sub(r"(\.\d+|\.clone)+$", "", name)


# ------------------------------------------------------------ intervals


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def intersect(xs, ys) -> list[tuple[float, float]]:
    """Intersection of two interval sets (each disjoint and sorted)."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys) -> list[tuple[float, float]]:
    """``xs`` minus ``ys`` (each disjoint and sorted)."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def self_times(events):
    """Each event's duration less that of the events nested in it
    (a ``while`` keeps only its own loop time), as [(start, self, name)]."""
    out = []
    stack: list[list] = []       # [end, self so far, start, name]
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            end, own, start, n = stack.pop()
            out.append((start, own, n))
        if stack:
            stack[-1][1] -= min(b, stack[-1][0]) - a
        stack.append([b, b - a, a, name])
    out += [(start, own, n) for end, own, start, n in stack]
    return out


def top_ops(trace: Trace, window, devices, n: int = 10):
    """The ``n`` operation kinds (names without numbering) with the most
    device seconds of their own inside ``window`` on ``devices``, as
    [[kind, seconds]]."""
    lo, hi = window
    secs: dict[str, float] = defaultdict(float)
    for d in devices:
        inside = [e for e in trace.ops.get(d, ()) if lo <= e[0] < hi]
        for _, own, name in self_times(inside):
            secs[category(name)] += own / 1e9
    return [[k, v] for k, v in sorted(secs.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_span(trace: Trace, held: dict[int, list], n: int = 10,
                 outside: str = "orchestrator"):
    """Idle device time inside each device's ``held`` intervals, summed
    by the innermost benchmark host span open at the middle of each
    gap (``outside`` where none is), as [[span, seconds]], most first."""
    spans = [s for s in trace.spans if s[2] != SPAN_PREFIX + "window"]
    starts = [s[0] for s in spans]
    secs: dict[str, float] = defaultdict(float)
    for d, intervals in held.items():
        for a, b in subtract(intervals, trace.busy(d)):
            mid = (a + b) / 2
            label, width = outside, math.inf
            # spans nest shallowly: look back over the last few starts
            for s0, s1, name in spans[max(bisect.bisect_right(starts, mid)
                                          - 8, 0):
                                      bisect.bisect_right(starts, mid)]:
                if s0 <= mid < s1 and s1 - s0 < width:
                    label, width = name, s1 - s0
            secs[label] += (b - a) / 1e9
    return [[k, v] for k, v in sorted(secs.items(), key=lambda kv: -kv[1])[:n]]
