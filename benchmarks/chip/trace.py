"""From a profiler trace to device busy time, idle share and a breakdown.

Two stages, so that the second can be checked on recorded events:

* :func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the
  operations of each device plane (``/device:TPU:<n>``, line
  ``XLA Ops``), and the host spans the benchmark itself opens with
  ``jax.profiler.TraceAnnotation`` around each call into the program.
  Device and host events share the profiler's clock.
* :func:`reduce` works on those events alone: the union of each device's
  operation intervals inside the traced window, averaged over devices, is
  ``busy_s``; the idle share is one minus ``busy_s`` over the window; each
  idle gap of the first device is charged to the innermost benchmark span
  that covers it, or to ``(no span)``. The operations that took most time
  are ranked by self time: a loop's body ops are not counted in the loop.
"""
from __future__ import annotations

import dataclasses
import gzip
import heapq
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
NO_SPAN = "(no span)"
TOP = 10


@dataclasses.dataclass
class Events:
    #: per device: [(start_ns, end_ns, name)] of its operations
    ops: dict[int, list[tuple[float, float, str]]]
    #: benchmark host spans: [(start_ns, end_ns, name)]
    spans: list[tuple[float, float, str]]


def op_name(text: str) -> str:
    """``fusion.54`` from ``%fusion.54 = f32[2000,200]{...} fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str, span_names) -> Events:
    """Events of an ``.xplane.pb`` file, or of its gzip (``.gz``)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    names = set(span_names) | {WINDOW_SPAN}
    ops: dict[int, list] = {}
    spans = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))] = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     op_name(ev.name)) for ev in line.events]
            elif not m:
                spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name) for ev in line.events
                             if ev.name in names)
    return Events(ops=ops, spans=spans)


def window(events: Events) -> tuple[float, float]:
    """The traced window: the benchmark's ``bench.window`` span."""
    wins = [(a, b) for a, b, n in events.spans if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(wins)}")
    return wins[0]


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b, *_ in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The complement of merged ``busy`` intervals inside ``[lo, hi]``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(idle, spans) -> dict[str, float]:
    """Nanoseconds of ``idle`` charged to the innermost (shortest) span
    that covers each instant; the rest to ``(no span)``."""
    marks = []
    for a, b in idle:
        marks += [(a, 1, 0, None), (b, 0, 0, None)]
    for i, (a, b, name) in enumerate(spans):
        marks += [(a, 1, 1, i), (b, 0, 1, i)]
    marks.sort(key=lambda m: (m[0], m[1]))
    heap: list[tuple[float, int]] = []
    live: set[int] = set()
    out: dict[str, float] = defaultdict(float)
    in_gap, t = False, None
    for x, opening, is_span, i in marks:
        if in_gap and t is not None and x > t:
            while heap and heap[0][1] not in live:
                heapq.heappop(heap)
            name = spans[heap[0][1]][2] if heap else NO_SPAN
            out[name] += x - t
        t = x
        if is_span:
            if opening:
                live.add(i)
                heapq.heappush(heap, (spans[i][1] - spans[i][0], i))
            else:
                live.discard(i)
        else:
            in_gap = bool(opening)
    return dict(out)


def self_times(ops, lo: float, hi: float) -> dict[str, float]:
    """Nanoseconds inside ``[lo, hi]`` of each operation name, less the
    time of operations nested in it (a loop and the ops of its body)."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []          # [end, name, own ns]

    def close():
        end, name, own = stack.pop()
        out[name] += own

    for a, b, name in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            close()
        own = max(0.0, min(b, hi) - max(a, lo))
        if stack:
            stack[-1][2] -= own
        stack.append([b, name, own])
    while stack:
        close()
    return dict(out)


def reduce(events: Events, lo: float, hi: float) -> dict:
    """Busy seconds averaged over devices, the window, and the breakdown."""
    if not events.ops:
        raise ValueError("the trace holds no device operations")
    busy = {d: union(ops, lo, hi) for d, ops in events.ops.items()}
    busy_ns = [sum(b - a for a, b in iv) for iv in busy.values()]
    first = min(busy)
    op_ns = self_times(events.ops[first], lo, hi)
    spans = [s for s in events.spans if s[2] != WINDOW_SPAN]
    idle = attribute(gaps(busy[first], lo, hi), spans)
    top = lambda d: [[k, v * 1e-9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "breakdown": {"device_ops": top(op_ns), "idle_gaps": top(idle)}}


def idle_percent(ctx: dict):
    """The traced window's idle share in percent, or ``None`` untraced."""
    t = ctx.get("trace")
    return None if not t else 100.0 * t["idle_share"]
