"""From a profiler trace (``.xplane.pb``) to device busy time, the device
operations that took most time, and the longest idle gaps with the host
span that covers each.

The arithmetic is on plain ``(start, end)`` pairs in seconds so that it can
be tested without a trace; ``read_xplane`` is the only part that touches
``jax.profiler.ProfileData``.

What a TPU trace looks like (my chip run, PR 23, jax 0.9): one plane per
chip named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO operation (leaf operations and the ``while``/``fusion``
parents that contain them, so intervals nest and the union is what counts);
``XLA Modules`` holds one event per executed program. Host threads are lines
of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear
there under the name given. All timestamps are nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Iterable, Optional

Interval = tuple[float, float]

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"
SPAN_PREFIX = "bench."


def union(intervals: Iterable[Interval]) -> list[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(busy: list[Interval], lo: float, hi: float) -> list[Interval]:
    """The idle intervals of [lo, hi] given disjoint sorted busy intervals."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


_SHAPE = re.compile(r"\{[^{}]*\}")
_OPERAND = re.compile(r"((?:[a-z]+[0-9]*)\[[0-9,]*\])")


def short_name(text: str, limit: int = 120) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction; keep the
    instruction's name, its opcode and kind, and the operand shapes without
    layouts: ``%fusion.7 fusion kCustom f32[56320,1728],s32[4561920]``."""
    if " = " not in text:
        return text[:limit]
    lhs, rhs = text.split(" = ", 1)
    rhs = _SHAPE.sub("", rhs)
    m = re.match(r"\s*(\([^()]*(?:\([^()]*\)[^()]*)*\)|\S+)\s+([\w\-]+)\((.*)", rhs, re.S)
    if not m:
        return lhs[:limit]
    opcode, rest = m.group(2), m.group(3)
    kind = re.search(r"kind=(\w+)", rest)
    shapes = ",".join(_OPERAND.findall(rest.split("), ")[0])[:6])
    out = " ".join(x for x in (lhs, opcode, kind.group(1) if kind else "", shapes) if x)
    return out[:limit]


def top_ops(events: Iterable[tuple[str, float, float]], n: int = 10) -> list:
    """[[name, seconds], ...]: the n names with the most summed duration."""
    total: dict = defaultdict(float)
    for name, s, e in events:
        total[short_name(name)] += e - s
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(
    idle: list[Interval], spans: Iterable[tuple[str, float, float]], n: int = 5
) -> list:
    """[[label, seconds], ...] for the n longest idle gaps; the label is
    the host span that overlaps the gap longest (innermost on a tie), or
    ``(no span)``."""
    spans = list(spans)
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        best, best_key = "(no span)", (0.0, 0.0)
        for name, ss, se in spans:
            ov = min(e, se) - max(s, ss)
            key = (ov, -(se - ss))  # longest overlap, then the shortest span
            if ov > 0 and key > best_key:
                best, best_key = name, key
        out.append([best, e - s])
    return out


def reduce(
    device_ops: dict, host_spans: list, window: Optional[Interval] = None
) -> dict:
    """``device_ops``: {device: [(name, start_s, end_s), ...]} of leaf and
    parent operations; ``host_spans``: [(name, start_s, end_s), ...].
    ``window`` defaults to the extent of the span named ``bench.window``,
    else to the extent of all device operations."""
    if window is None:
        win = [(s, e) for n, s, e in host_spans if n == SPAN_PREFIX + "window"]
        if win:
            window = (min(s for s, _ in win), max(e for _, e in win))
        else:
            every = [(s, e) for ops in device_ops.values() for _, s, e in ops]
            if not every:
                raise ValueError("the trace holds no device operation")
            window = (min(s for s, _ in every), max(e for _, e in every))
    lo, hi = window
    per_device, merged_ops = [], []
    fullest: list[Interval] = []
    for ops in device_ops.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]
        busy = union((s, e) for _, s, e in inside)
        per_device.append(sum(e - s for s, e in busy))
        merged_ops.extend(inside)
        if not fullest or per_device[-1] == max(per_device):
            fullest = busy
    if not per_device or max(per_device) <= 0:
        raise ValueError("no device operation ran inside the traced window")
    idle = gaps(fullest, lo, hi)
    named = [x for x in host_spans if x[0] != SPAN_PREFIX + "window"]
    return {
        "busy_s": sum(per_device) / len(per_device),
        "window_s": hi - lo,
        "device_ops": top_ops(merged_ops, 10),
        "idle_gaps": label_gaps(idle, named, 10),
    }


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str) -> tuple[dict, list, dict]:
    """(device_ops, host_spans, layout) of one ``.xplane.pb``. Leaf device
    operations only would be best for the top-ops table, but a parent
    (``while``) and its children both appear on ``XLA Ops``; the table keeps
    them all, and the busy time is a union, so nothing is counted twice
    there. ``layout`` names every plane and line, for PERF.md."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: dict = {}
    host_spans: list = []
    layout: dict = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = len(events)
            if plane.name.startswith(DEVICE_PLANE_PREFIX) and line.name == OPS_LINE:
                device_ops[plane.name] = [
                    (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in events
                ]
            elif plane.name.startswith(HOST_PLANE_PREFIX):
                host_spans.extend(
                    (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in events
                    if ev.name.startswith(SPAN_PREFIX)
                )
        layout[plane.name] = lines
    return device_ops, host_spans, layout


def reduce_trace_dir(trace_dir: str) -> dict:
    device_ops, host_spans, layout = read_xplane(find_xplane(trace_dir))
    out = reduce(device_ops, host_spans)
    out["layout"] = layout
    return out
