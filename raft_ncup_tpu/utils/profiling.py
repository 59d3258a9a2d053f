"""Profiling: captures, the span bridge, and the reduction of a capture.

The reference ships no profiler hooks or timers (SURVEY.md §5 "tracing").
Here, one span system on the profiler's clock:

- :func:`trace` captures a ``jax.profiler`` trace into a directory
  (``train.py --profile_steps``, ``evaluate.py`` / ``serve.py
  --trace_dir``) and leaves beside it
  ``op_scopes.json``: which ``jax.named_scope`` each compiled
  instruction belongs to, from the cost ledger's compile-time bank
  (``inference/costs.py``), because a TPU trace's ``XLA Ops`` events
  carry the instruction and no ``op_name`` (my chip run, PR 24).
- :func:`annotate_spans` bridges a telemetry hub's span tracer to the
  profiler: every ``hub.span(...)`` context also holds a
  ``jax.profiler.TraceAnnotation`` of the same name, so in a capture the
  program's spans lie on ``/host:CPU`` on the nanosecond clock of the
  device's ``XLA Ops``. ``observability/`` may not import jax (JGL010),
  so the factory is installed from here by the jax-side owners of a hub
  (``ShapeCachedForward``, ``DevicePrefetcher``).
- :func:`scope_seconds` and :func:`label_gaps` reduce a capture to
  device seconds per ``raft.*`` scope and to idle gaps labelled by the
  program's spans; the arithmetic is on plain tuples so that it is
  tested without a trace, and :func:`read_device_trace` is the only
  part that touches ``jax.profiler.ProfileData``.
  ``scripts/device_trace_report.py`` prints it.
- :class:`CompileMeter` listens to ``jax.monitoring``'s compile and
  persistent-cache events; :func:`compile_meter` is the process's one
  instance, installed by ``ShapeCachedForward`` and ``open_train_run``,
  and :func:`timed_build` splits a program's build into its
  ``startup_trace_lower`` and ``startup_compile`` phases with the cache's
  verdict (docs/OBSERVABILITY.md "Start-up timeline").
"""

from __future__ import annotations

import collections
import contextlib
import glob
import heapq
import json
import os
import re
import threading
from typing import Iterable, Iterator, Optional

import jax

from raft_ncup_tpu.observability.startup import StartupPhase

# The stat that marks a host event as one of the program's spans (and not
# one of the runtime's own TraceMe events, which share the host plane).
SPAN_MARK = "program_span"
OP_SCOPES_FILE = "op_scopes.json"
UNSCOPED = "unscoped"

_SCOPE = re.compile(r"\b(?:raft|train|stream)\.[a-z_]+(?:\.[a-z_]+)*")


def _annotation(name: str, attrs: dict):
    """One span's profiler annotation: its name, the mark, and its scalar
    correlation attributes (``batch_id``, ``pass_id``...) as event stats.
    Outside a capture the runtime builds none of it."""
    stats = {k: v for k, v in attrs.items() if isinstance(v, (int, float, str))}
    return jax.profiler.TraceAnnotation(name, **{SPAN_MARK: 1}, **stats)


def annotate_spans(telemetry) -> None:
    """Put ``telemetry``'s spans on the profiler's timeline (idempotent;
    a disabled hub hands out no span and so enters no annotation)."""
    telemetry.tracer.annotate = _annotation


class CompileMeter:
    """XLA compiles, their wall seconds and persistent-cache hits/misses,
    from jax.monitoring — the same event analysis/guards.py counts. A
    program loaded from the persistent cache fires the duration event too
    (with the seconds the load took), so ``compiles`` counts programs
    compiled OR loaded. An instance counts from its construction on;
    listeners cannot be taken off again, so the program shares one
    (:func:`compile_meter`)."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self._COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def snapshot(self) -> tuple:
        return (self.compiles, self.compile_s, self.hits, self.misses)

    def totals(self) -> dict:
        """The counts under the start-up record's names
        (``observability/startup.py``)."""
        return {
            "programs_loaded": self.compiles, "compile_s": self.compile_s,
            "cache_hits": self.hits, "cache_misses": self.misses,
        }


_meter_lock = threading.Lock()
_meter: Optional[CompileMeter] = None


def compile_meter() -> CompileMeter:
    """The process's one listener (idempotent): every compile-or-load
    event of the process from its first call on, the benchmark's own
    reference programs included where a benchmark shares the process."""
    global _meter
    with _meter_lock:
        if _meter is None:
            _meter = CompileMeter()
        return _meter


def timed_build(hub, jitfn, args: tuple, *, key: str, kind: str) -> tuple:
    """``jitfn.lower(*args).compile()`` as its two start-up phases on
    ``hub``: ``startup_trace_lower`` (Python trace to jaxpr + lowering to
    StableHLO: all host, paid warm and cold) and ``startup_compile`` (the
    backend's compile or the persistent cache's load), the second with
    the cache's verdict from the monitoring events fired between its
    ends: ``miss`` (an entry was written), ``hit`` (one was read), ``off``
    (neither: no cache, as on the CPU backend). Returns ``(compiled,
    phases)``; ``phases`` holds ``trace_lower_s``, ``compile_s``,
    ``cache`` and ``programs`` (compile events between the ends: 1 unless
    the backend compiles helpers)."""
    meter = compile_meter()
    with StartupPhase(hub, "startup_trace_lower", key=key, kind=kind) as lower:
        lowered = jitfn.lower(*args)
    with StartupPhase(hub, "startup_compile", key=key, kind=kind) as build:
        c0, _, h0, m0 = meter.snapshot()
        compiled = lowered.compile()
        c1, _, h1, m1 = meter.snapshot()
        cache = "miss" if m1 > m0 else "hit" if h1 > h0 else "off"
        build.set(cache=cache, programs=c1 - c0)
    return compiled, {
        "trace_lower_s": lower.seconds, "compile_s": build.seconds,
        "cache": cache, "programs": c1 - c0,
    }


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a device trace into ``log_dir`` (no-op when None), and
    write ``op_scopes.json`` beside it when the capture stops.

    View with TensorBoard's profile plugin or Perfetto; reduce with
    ``scripts/device_trace_report.py``.
    """
    if log_dir is None:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        from raft_ncup_tpu.inference.costs import get_cost_ledger

        with open(os.path.join(log_dir, OP_SCOPES_FILE), "w") as f:
            json.dump(get_cost_ledger().op_scopes(), f)


# ------------------------------------------------ reducing a device trace
#
# What a TPU capture holds (my chip runs, PR 23 and PR 24, jax 0.9): plane
# ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per executed
# program, named ``<hlo module>(<id>)``) and ``XLA Ops`` (one event per
# executed instruction, named by the instruction's whole text; a ``while``
# and the operations of its body both appear, so intervals nest). An ``XLA
# Ops`` event's stats are its device offset and duration and nothing else:
# the ``jax.named_scope`` path lives only in the compiled module's text
# (``metadata={op_name="jit(fn)/raft.refinement/while/body/closed_call/
# raft.corr_lookup/reduce_sum"}``; scopes inside the ``while`` body survive),
# so the join is on the instruction's name. Host threads are lines of
# ``/host:CPU``; a ``TraceAnnotation``'s keyword arguments are its stats.

ScopedOp = tuple  # (name, scope or None, start_s, end_s)
HostSpan = tuple  # (name, start_s, end_s)

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body)=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) \(.*\{\s*$")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``raft.*``, ``train.*`` or ``stream.*`` scope of an
    ``op_name`` path, None when the path lies in none. In a differentiated program (the
    training step) the phase is appended: the forward's operations keep
    the plain name (``.../jvp(raft.fnet)/...``), the backward's are
    ``<scope>.bwd`` (``.../transpose(jvp(raft.refinement))/while/body/
    closed_call/checkpoint/raft.update_block/...``) and the forward that a
    ``jax.checkpoint`` runs again inside the backward is ``<scope>.remat``
    (``.../checkpoint/rematted_computation/raft.update_block/...``)."""
    found = _SCOPE.findall(op_name)
    if not found:
        return None
    if "rematted_computation" in op_name:
        return found[-1] + ".remat"
    if "transpose(" in op_name:
        return found[-1] + ".bwd"
    return found[-1]


def hlo_op_scopes(hlo_text: str) -> dict:
    """``{instruction name: scope}`` of one compiled module's text. An
    instruction is in the innermost scope (:func:`scope_of`) of its own ``op_name``;
    a fusion (or ``while``) whose own ``op_name`` names none takes the scope
    most of the instructions it calls are in. Instructions in no scope are
    left out."""
    own: dict = {}
    called: dict = {}
    members: dict = {}
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else None
        if scope is not None:
            own[name] = scope
            counts = members.setdefault(computation, {})
            counts[scope] = counts.get(scope, 0) + 1
        else:
            callee = _CALLED.search(line)
            if callee:
                called[name] = callee.group(1)
    for name, callee in called.items():
        counts = members.get(callee)
        if counts:
            own[name] = max(counts, key=counts.get)
    return own


def union(intervals: Iterable[tuple]) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out: list = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def scope_seconds(ops: Iterable[ScopedOp]) -> dict:
    """Device seconds per scope from the events of ONE device line, by self
    time: an event's duration less its children's, so a ``while`` and the
    fusions of its body are not counted twice and the values sum to the
    union of the intervals. An event with scope None takes its parent's
    (what is in the loop and in no inner scope is ``raft.refinement``),
    and ``unscoped`` where it has no parent."""
    total: dict = {}
    stack: list = []  # [scope, end_s, self_s]

    def close() -> None:
        scope, _, self_s = stack.pop()
        total[scope] = total.get(scope, 0.0) + self_s

    # (start, -end) order puts a parent before its children; the part of a
    # child that overruns its parent is queued again as an event of its own.
    queue = [(s, -e, scope) for _, scope, s, e in ops if e > s]
    heapq.heapify(queue)
    while queue:
        s, neg_e, scope = heapq.heappop(queue)
        e = -neg_e
        while stack and s >= stack[-1][1]:
            close()
        if stack:
            parent_scope, parent_end, _ = stack[-1]
            if e > parent_end:
                heapq.heappush(queue, (parent_end, -e, scope))
                e = parent_end
            stack[-1][2] -= e - s
            scope = scope or parent_scope
        stack.append([scope or UNSCOPED, e, e - s])
    while stack:
        close()
    return total


def label_gaps(idle: list, spans: Iterable[HostSpan], n: int = 5) -> list:
    """The ``n`` longest idle gaps, each with the seconds of it the program's
    spans overlap, summed by name (``spans``), and as ``label`` the name
    that overlaps it longest (on a tie the one whose spans are shorter,
    which is the innermost), or ``(no span)``."""
    spans = list(spans)
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        overlaps: dict = {}
        length: dict = {}
        for name, ss, se in spans:
            ov = min(e, se) - max(s, ss)
            if ov > 0:
                overlaps[name] = overlaps.get(name, 0.0) + ov
                length[name] = length.get(name, 0.0) + (se - ss)
        label = max(
            overlaps, key=lambda k: (overlaps[k], -length[k]), default="(no span)"
        )
        out.append({"seconds": e - s, "start_s": s, "label": label, "spans": overlaps})
    return out


def reduce_device_trace(ops: Iterable[ScopedOp], spans: Iterable[HostSpan]) -> dict:
    """One device's events and the program's spans to the report: busy and
    idle over the extent of both, seconds per scope, the longest gaps."""
    ops, spans = list(ops), list(spans)
    if not ops:
        raise ValueError("the trace holds no device operation")
    busy = union((s, e) for _, _, s, e in ops)
    lo = min([busy[0][0]] + [s for _, s, _ in spans])
    hi = max([busy[-1][1]] + [e for _, _, e in spans])
    idle, cur = [], lo
    for s, e in busy:
        if s > cur:
            idle.append((cur, s))
        cur = e
    if hi > cur:
        idle.append((cur, hi))
    scopes = scope_seconds(ops)
    return {
        "window_s": hi - lo,
        "busy_s": sum(e - s for s, e in busy),
        "scope_s": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
        "scope_sum_s": sum(scopes.values()),
        "idle_gaps": [
            {**g, "start_s": g["start_s"] - lo} for g in label_gaps(idle, spans, 10)
        ],
    }


def find_xplane(trace_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_device_trace(path: str, op_scopes: Optional[dict] = None) -> tuple:
    """``(device_ops, program_spans)`` of one ``.xplane.pb``: per device
    plane its ``XLA Ops`` events as ``(name, scope, start_s, end_s)``, the
    scope joined from ``op_scopes`` (``{hlo module: {instruction: scope}}``)
    through the ``XLA Modules`` event each operation ran inside; and the
    host events that carry :data:`SPAN_MARK`."""
    from jax.profiler import ProfileData

    op_scopes = op_scopes or {}
    device_ops: dict = {}
    spans: list = []
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("(")[0])
                for ev in lines["XLA Modules"].events
            ) if "XLA Modules" in lines else []
            ops, k = [], 0
            for ev in sorted(lines["XLA Ops"].events, key=lambda ev: ev.start_ns):
                while k < len(modules) and modules[k][1] <= ev.start_ns:
                    k += 1
                inside = k < len(modules) and modules[k][0] <= ev.start_ns
                scopes = op_scopes.get(modules[k][2], {}) if inside else {}
                name = ev.name.split(" = ", 1)[0].lstrip("%")
                ops.append((
                    name, scopes.get(name), ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9,
                ))
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if any(k == SPAN_MARK for k, _ in ev.stats):
                        spans.append((
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9,
                        ))
    return device_ops, spans


def device_trace_report(trace_dir: str) -> dict:
    """The report of the newest capture under ``trace_dir`` (what
    ``scripts/device_trace_report.py`` prints): how many events each of
    the program's spans left on the host plane, and one entry per device
    (none in a CPU capture, which has no device plane)."""
    scopes_path = os.path.join(trace_dir, OP_SCOPES_FILE)
    op_scopes = {}
    if os.path.isfile(scopes_path):
        with open(scopes_path) as f:
            op_scopes = json.load(f)
    path = find_xplane(trace_dir)
    device_ops, spans = read_device_trace(path, op_scopes)
    counts = collections.Counter(name for name, _, _ in spans)
    return {
        "xplane": path,
        "program_spans": dict(sorted(counts.items())),
        "devices": {
            name: reduce_device_trace(ops, spans) for name, ops in device_ops.items()
        },
    }
