"""The start-up timeline: what a process paid before its first timed
request or step, per executable and per phase, kept where no window's
reset reaches it (docs/OBSERVABILITY.md "Start-up timeline").

Two pieces, both host-only stdlib (JGL010):

- :class:`StartupPhase` — one start-up phase as a context manager: the
  owning hub's ``span`` of the same name (ring record, ``{name}_ms``
  histogram, and in a capture the profiler's timeline) plus the phase's
  seconds on ``time.perf_counter``, read whether or not the hub is
  enabled and whatever clock a test injected into it. The six phases:
  ``startup_trace_lower``, ``startup_compile``, ``startup_first_run``
  (per executable; ``inference/costs.build_and_record`` and
  ``first_run``), ``startup_weights`` (``training/loop.
  open_train_run``), ``input_start`` (``DevicePrefetcher``) and
  ``startup_warmup`` (``FlowServer.warmup`` / ``StreamEngine.warmup``).
- :class:`StartupRecord` — the process-wide, append-only bank of those
  seconds. The windowed benchmark drivers reset the hub (or run on a
  private one) at the window's start, so a reader at the end of a run
  finds none of set-up's histograms; this record is touched by no
  ``reset()``. Bounded: the first :data:`MAX_PROGRAMS` executables are
  kept, later ones are counted in ``dropped``.

:func:`startup_report` is the one accessor (also ``FlowServer.report()
["startup"]`` and ``StreamEngine.report()["startup"]``), and
:func:`startup_line` the operator's one line (``serve.py``,
``evaluate.py``, ``train.py`` print it when warm-up ends).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

MAX_PROGRAMS = 64

# The process-level phases. Summed over the process (a server may warm
# several shapes, a process may open several runs) but for the one kept
# from its first occurrence only: every eval pass opens an input
# pipeline, and only the first is set-up (the rest are in
# ``input_start_ms`` on the hub).
_PHASES = ("weights_s", "input_start_s", "warmup_s")
_FIRST_ONLY = frozenset({"input_start_s"})
_PROCESS = ("programs_loaded", "cache_hits", "cache_misses", "compile_s")


class StartupPhase:
    """``with StartupPhase(hub, "startup_compile", key=k) as phase: ...``
    — the hub's span of that name around the body, and ``phase.seconds``
    after it. The start is read at construction (as a span's is), so a
    phase may be built on one thread and entered on another
    (``DevicePrefetcher``'s ``input_start``)."""

    __slots__ = ("seconds", "_ctx", "_span", "_t0")

    def __init__(self, hub, name: str, **attrs):
        self._ctx = hub.span(name, **attrs)
        self._span = None
        self.seconds: Optional[float] = None
        self._t0 = time.perf_counter()

    def __enter__(self) -> "StartupPhase":
        self._span = self._ctx.__enter__()
        return self

    def set(self, **attrs) -> None:
        self._span.set(**attrs)

    def discard(self) -> None:
        """Nothing started (an empty iterator): no span, no seconds."""
        self._span.discard()
        self._t0 = None

    def __exit__(self, *exc) -> None:
        if self._t0 is not None:
            self.seconds = time.perf_counter() - self._t0
        self._ctx.__exit__(*exc)


class StartupRecord:
    """Thread-safe bank of the start-up phases (module docstring)."""

    def __init__(self):
        self._programs: dict = {}  # key -> entry, in order of first build
        self._dropped = 0
        self._phases: dict = {}
        self._process: dict = {}
        self._lock = threading.Lock()

    def program(
        self, key: str, kind: str, *, trace_lower_s: float,
        compile_s: float, cache: str, probe_s: Optional[float] = None,
        process: Optional[dict] = None, precision: Optional[dict] = None,
        saved_residuals: Optional[dict] = None,
        contract_forms: Optional[dict] = None,
        conv_forms: Optional[dict] = None,
    ) -> None:
        """One executable built: its two build phases, the persistent
        cache's verdict, the compile listener's process totals as they
        stood when the build ended (``process``), and under which
        precision policy how many of its product sites took bfloat16 and
        float32 operands (``precision``: ``{"policy", "sites_bf16",
        "sites_f32"}``, from ``precision/sites.py``'s tally of the build's
        trace), and how many values the program's checkpoint policy kept
        under each of its names (``saved_residuals``: ``utils/remat.py``'s
        tally of the same trace; the training step's), and which form each
        level of its ``volume`` lookup took over which stored dtype
        (``contract_forms``: ``ops/corr.py``'s tally of the same trace,
        ``{"level0": "dot/bfloat16", ...}``), and which of its ``Conv2d``
        sites were not computed as the ``conv_general_dilated`` they are
        written as (``conv_forms``: ``nn/layers.py``'s tally of the same
        trace without its ``conv`` list, ``{"folded_in": ["encoder/convf1"],
        "folded_out": ["flow_head/conv2"], "phased_in": ["conv1"]}``). A key
        built again (an LRU eviction, a second run in one process) adds to
        its entry's seconds and takes the newest verdict."""
        with self._lock:
            entry = self._programs.get(key)
            if entry is None and len(self._programs) >= MAX_PROGRAMS:
                self._dropped += 1
            else:
                if entry is None:
                    entry = self._programs[key] = {
                        "key": key, "kind": kind, "trace_lower_s": 0.0,
                        "compile_s": 0.0, "cache": cache,
                        "first_run_s": None, "probe_s": None, "builds": 0,
                    }
                entry["trace_lower_s"] += float(trace_lower_s)
                entry["compile_s"] += float(compile_s)
                entry["cache"] = cache
                entry["builds"] += 1
                if precision is not None:
                    entry["precision"] = dict(precision)
                if saved_residuals is not None:
                    entry["saved_residuals"] = dict(saved_residuals)
                if contract_forms is not None:
                    entry["contract_forms"] = dict(contract_forms)
                if conv_forms is not None:
                    entry["conv_forms"] = {f: list(s) for f, s in conv_forms.items()}
                if probe_s is not None:
                    entry["probe_s"] = (entry["probe_s"] or 0.0) + float(probe_s)
            if process is not None:
                self._process = {k: process[k] for k in _PROCESS}

    def first_run(self, key: str, seconds: float) -> None:
        """The first call of the executable built under ``key``."""
        with self._lock:
            entry = self._programs.get(key)
            if entry is not None:
                entry["first_run_s"] = (entry["first_run_s"] or 0.0) + float(seconds)

    def phase(self, name: str, seconds: Optional[float]) -> None:
        """One process-level phase (``weights_s``, ``warmup_s``: summed;
        ``input_start_s``: the process's first only). ``None`` (a
        discarded phase) records nothing."""
        if seconds is None:
            return
        if name not in _PHASES:
            raise KeyError(f"no start-up phase {name!r}")
        with self._lock:
            if name in _FIRST_ONLY:
                self._phases.setdefault(name, float(seconds))
            else:
                self._phases[name] = self._phases.get(name, 0.0) + float(seconds)

    def report(self) -> dict:
        with self._lock:
            return {
                "programs": [dict(e) for e in self._programs.values()],
                "phases": {k: self._phases.get(k) for k in _PHASES},
                "process": {k: self._process.get(k) for k in _PROCESS},
                "dropped": self._dropped,
            }


_lock = threading.Lock()
_record: Optional[StartupRecord] = None


def get_startup_record() -> StartupRecord:
    """The process-wide record (created on first use)."""
    global _record
    with _lock:
        if _record is None:
            _record = StartupRecord()
        return _record


def set_startup_record(record: Optional[StartupRecord]) -> Optional[StartupRecord]:
    """Swap the process record (test isolation); returns the previous."""
    global _record
    with _lock:
        prev, _record = _record, record
        return prev


def startup_report() -> dict:
    """``{"programs": [{"key", "kind", "trace_lower_s", "compile_s",
    "cache", "first_run_s", "precision": {"policy", "sites_bf16",
    "sites_f32"}, "saved_residuals": {name: count}, "contract_forms":
    {level: "form/dtype"}, "conv_forms": {"folded_in" | "folded_out" |
    "phased_in": [site]}, ...}], "phases":
    {"weights_s", "input_start_s", "warmup_s"}, "process": {"programs_loaded",
    "cache_hits", "cache_misses", "compile_s"}, "dropped"}`` — a phase
    the process has not run reads ``None``."""
    return get_startup_record().report()


def startup_line(report: Optional[dict] = None) -> str:
    """``startup: trace+lower 4.1 s, load 2.5 s (2 hit, 0 miss), first
    run 1.2 s, weights 0.3 s, input 0.4 s`` — the answer to "why did
    this process take two minutes to come up"; phases it has not run are
    left out."""
    report = startup_report() if report is None else report
    programs = report["programs"]
    verdicts = [p["cache"] for p in programs]
    parts = [
        f"trace+lower {sum(p['trace_lower_s'] for p in programs):.1f} s",
        f"load {sum(p['compile_s'] for p in programs):.1f} s "
        f"({verdicts.count('hit')} hit, {verdicts.count('miss')} miss"
        + (f", {verdicts.count('off')} uncached" if "off" in verdicts else "")
        + ")",
        f"first run {sum(p['first_run_s'] or 0.0 for p in programs):.1f} s",
    ]
    for label, name in (("weights", "weights_s"), ("input", "input_start_s"),
                        ("warm-up", "warmup_s")):
        if report["phases"].get(name) is not None:
            parts.append(f"{label} {report['phases'][name]:.1f} s")
    if report["dropped"]:
        parts.append(f"{report['dropped']} more programs not listed")
    return "startup: " + ", ".join(parts)
