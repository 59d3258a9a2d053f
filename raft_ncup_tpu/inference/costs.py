"""The compiled-executable cost ledger: what each warmed program
actually costs, recorded ONCE at compile time (docs/PERF.md
"flops_per_pair and MFU").

Every bench row before this module reported ``mfu: null`` because
nothing recorded what the compiled executables cost — the analytic
estimate in ``utils/flops.py`` exists, but MFU against a spec-sheet
peak is only honest when the numerator is XLA's own accounting for the
program that actually ran. This module closes that gap with one
declarative object (the ``FleetConfig``/``PrecisionPolicy`` pattern
applied to cost accounting): a process-wide :class:`CostLedger` that
``ShapeCachedForward`` feeds at warm-up/compile time and that bench,
``scripts/flip_recommendations.py``, and the future autotuner
(ROADMAP item 1) all read.

Per warmed executable the ledger holds:

- ``flops`` / ``bytes_accessed`` from ``Compiled.cost_analysis()``,
- ``compile_ms`` (wall time of ``lower().compile()``): since the
  start-up timeline (docs/OBSERVABILITY.md) the SUM of its two phases,
  ``trace_lower_ms`` (Python trace + lowering, all host) and
  ``backend_compile_ms`` (the backend's compile or the persistent
  cache's load), which sit beside it with the cache's verdict ``cache``
  (``hit`` / ``miss`` / ``off``), ``programs`` (compile events in the
  second phase), ``probe_ms`` (what this ledger's own analysis of the
  executable took) and, after the executable's first call,
  ``first_run_ms`` (:func:`build_and_record`, :func:`first_run`),
- ``product_sites``: which product site of the program took operands of
  which dtype (``precision/sites.py``: the trace-time tally of the build's
  lowering, ``{"<scope>/<site>": {"operands", "result"}}``); the start-up
  report carries its summary beside the phases,
- ``memory_stats`` from ``Compiled.memory_analysis()``
  (argument/output/temp/generated-code bytes — the
  ``compiled_memory_stats`` surface),
- ``op_scopes``: which ``jax.named_scope`` each instruction of the
  compiled module lies in (``utils/profiling.hlo_op_scopes`` over
  ``Compiled.as_text()``), under the module's name — a device trace
  names instructions and carries no ``op_name``, so the per-scope
  reduction of a capture joins on this bank
  (:meth:`CostLedger.op_scopes`; kept out of ``snapshot()``),

keyed by ``"<backend>|<executable key>"`` where the executable key is
the SAME tuple that keys the compiled-program LRU (mesh fingerprint,
padded shape, iters, precision fingerprint...) — so the ledger key is
stable across re-warms by construction: same shape ⇒ same key, and a
re-warm that hits the LRU records nothing twice.

Forward/metric entries' ``meta`` additionally carries the correlation
tuning knobs the executable was traced with
(``ops.corr.corr_tuning_meta``: onthefly ``corr_row_chunk``, Pallas
``corr_query_block`` / ``corr_band_rows``) — the first real knob
surface for the ROADMAP item-1 autotuner, persisted right next to the
cost facts a sweep would optimize, under the same stable keys its
tuning cache will use.

**Why this lives here and not in observability/**: reading XLA cost
analysis requires jax, and ``observability/`` is host-only stdlib by
lint rule JGL010 — telemetry must never be able to initialize a
backend. The probe therefore sits WITH the inference machinery that
already owns the compiles (``inference/pipeline.py``), runs only at
compile time (never on the hot path — a warmed call pays one dict
read), and hands downstream consumers plain host dicts.

**MFU** = achieved FLOP/s over the chip's peak. :func:`peak_flops` is
the per-backend peak table: TPU chips by ``device_kind`` from the spec
sheet (``utils/flops.TPU_PEAK_FLOPS``), CPU from a nominal per-core figure
(overridable via ``RAFT_NCUP_CPU_PEAK_FLOPS``) so CPU rows report a
real — if humbling — utilization instead of ``null``. ``None`` means
the BACKEND is unknown, never "we didn't measure": the moment a chip
answers, the same code path reports real MFU with zero new code.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

from raft_ncup_tpu.nn import layers
from raft_ncup_tpu.observability.startup import (
    StartupPhase,
    get_startup_record,
)
from raft_ncup_tpu.ops import corr
from raft_ncup_tpu.precision import sites
from raft_ncup_tpu.utils import remat
from raft_ncup_tpu.utils.flops import TPU_PEAK_FLOPS
from raft_ncup_tpu.utils.knobs import knob_enabled, knob_raw

COST_LEDGER_ENV = "RAFT_NCUP_COST_LEDGER"
CPU_PEAK_ENV = "RAFT_NCUP_CPU_PEAK_FLOPS"

# Nominal peak per CPU core: 8-lane f32 FMA (AVX2) at ~3 GHz = 2 * 8 *
# 3e9 = 4.8e10 FLOP/s. Deliberately a round spec-sheet-style constant,
# not a microbenchmark: CPU MFU is an order-of-magnitude sanity figure
# (documented in docs/PERF.md), and the env override exists for hosts
# where the nominal is far off.
CPU_PEAK_FLOPS_PER_CORE = 4.8e10

_MEMORY_STAT_FIELDS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
)


def peak_flops(
    backend: Optional[str], device_kind: Optional[str] = None
) -> Optional[float]:
    """Peak dense FLOP/s per chip. On ``tpu`` the lookup is keyed by the
    real ``device_kind`` string (``utils/flops.TPU_PEAK_FLOPS``) and an
    unknown chip raises: a silently-null ``mfu`` on the chip the project
    targets is exactly the failure this guards. ``None`` only for a
    backend that has no table at all."""
    if not backend:
        return None
    backend = backend.lower()
    if backend == "cpu":
        override = knob_raw(CPU_PEAK_ENV)
        if override:
            try:
                return float(override)
            except ValueError:
                pass
        return (os.cpu_count() or 1) * CPU_PEAK_FLOPS_PER_CORE
    if backend == "tpu":
        if device_kind not in TPU_PEAK_FLOPS:
            raise KeyError(
                f"no peak FLOP/s entry for TPU device_kind {device_kind!r}; "
                "add it to utils/flops.TPU_PEAK_FLOPS with its source"
            )
        return TPU_PEAK_FLOPS[device_kind]
    return None


def mfu(
    flops_per_item: Optional[float],
    items_per_sec: Optional[float],
    peak: Optional[float],
) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over ``peak``. ``None``
    when any input is unknown (an unknown backend, an unmeasured
    executable) — never 0.0, which would claim a measurement."""
    if not flops_per_item or not items_per_sec or not peak:
        return None
    return round(flops_per_item * items_per_sec / peak, 6)


def probe_compiled(compiled) -> dict:
    """Harvest one AOT-compiled executable's cost facts as a host dict:
    ``{"flops", "bytes_accessed", "memory_stats"}``. Best-effort per
    field — an XLA build that lacks one analysis yields ``None`` for
    that field, never an exception (the probe must not be able to take
    a warmup down)."""
    out: dict = {"flops": None, "bytes_accessed": None,
                 "memory_stats": {}, "module": None, "op_scopes": {}}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            if ca.get("flops"):
                out["flops"] = float(ca["flops"])
            # XLA's key really does contain a space.
            if ca.get("bytes accessed"):
                out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:  # pragma: no cover - backend-specific
        pass
    try:
        ma = compiled.memory_analysis()
        out["memory_stats"] = {
            f: int(getattr(ma, f))
            for f in _MEMORY_STAT_FIELDS
            if getattr(ma, f, None) is not None
        }
    except Exception:  # pragma: no cover - backend-specific
        pass
    try:
        from raft_ncup_tpu.utils.profiling import hlo_op_scopes

        text = compiled.as_text()
        out["module"] = text.split(",", 1)[0].split()[-1]  # "HloModule <name>, ..."
        out["op_scopes"] = hlo_op_scopes(text)
    except Exception:  # pragma: no cover - backend-specific
        pass
    return out


class CostLedger:
    """Thread-safe per-process ledger of compiled-executable costs.

    ``record_compiled`` is called by the compile probe exactly once per
    (backend, executable key); re-recording the same key overwrites in
    place (idempotent — the entry describes the executable, not the
    event). ``meta`` carries the structured identity the consumers
    filter on (kind/shape/iters), parsed from the executable key by the
    probe so bench never reverse-engineers key strings.
    """

    def __init__(self, enabled: Optional[bool] = None):
        self.enabled = (
            knob_enabled(COST_LEDGER_ENV)
            if enabled is None else bool(enabled)
        )
        self._entries: Dict[str, dict] = {}
        self._lock = threading.Lock()

    def record_compiled(
        self, key: str, compiled, *, backend: Optional[str] = None,
        phases: Optional[dict] = None, **meta,
    ) -> Optional[dict]:
        """``phases`` (``utils/profiling.timed_build``): the build's two
        start-up phases; ``compile_ms`` is their sum, unrounded, so that
        the three agree to the last digit (``None`` for an executable
        banked without its build)."""
        if not self.enabled:
            return None
        t0 = time.perf_counter()
        entry = probe_compiled(compiled)
        entry["probe_ms"] = (time.perf_counter() - t0) * 1e3
        entry["key"] = str(key)
        entry["backend"] = backend
        if phases is not None:
            entry["trace_lower_ms"] = phases["trace_lower_s"] * 1e3
            entry["backend_compile_ms"] = phases["compile_s"] * 1e3
            entry["cache"] = phases["cache"]
            entry["programs"] = phases["programs"]
            entry["first_run_ms"] = None
            entry["compile_ms"] = (
                entry["trace_lower_ms"] + entry["backend_compile_ms"]
            )
        else:
            entry["compile_ms"] = None
        entry["meta"] = {k: v for k, v in meta.items() if v is not None}
        with self._lock:
            self._entries[str(key)] = entry
        return entry

    def record_first_run(self, key: str, ms: float) -> None:
        """The first call of the executable banked under ``key``."""
        with self._lock:
            entry = self._entries.get(str(key))
            if entry is not None:
                entry["first_run_ms"] = float(ms)

    # ---------------------------------------------------------- consumers

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def entry(self, key: str) -> Optional[dict]:
        with self._lock:
            return self._entries.get(str(key))

    def keys(self) -> list:
        with self._lock:
            return sorted(self._entries)

    def lookup(self, **meta) -> Optional[dict]:
        """First entry whose ``meta`` matches every given item (e.g.
        ``lookup(kind="forward", shape=(1, 96, 128, 3), iters=12)``) —
        how bench finds the warmed headline executable's costs."""
        with self._lock:
            entries = list(self._entries.values())
        for e in entries:
            m = e.get("meta") or {}
            if all(m.get(k) == v for k, v in meta.items()):
                return e
        return None

    def op_scopes(self) -> dict:
        """``{hlo module name: {instruction name: scope}}`` over every
        banked executable — the join table of a device trace's per-scope
        reduction (``utils/profiling.read_device_trace``). Programs that
        share a module name (every shape of ``jit(fn)``) number their
        instructions independently: an instruction they place in
        different scopes is left out, so it reads as ``unscoped`` rather
        than as the wrong scope."""
        with self._lock:
            entries = list(self._entries.values())
        merged: dict = {}
        clash: dict = {}
        for e in entries:
            into = merged.setdefault(e.get("module"), {})
            for name, scope in (e.get("op_scopes") or {}).items():
                if into.setdefault(name, scope) != scope:
                    clash.setdefault(e.get("module"), set()).add(name)
        for module, names in clash.items():
            for name in names:
                del merged[module][name]
        return {m: v for m, v in merged.items() if m and v}

    def snapshot(self) -> dict:
        """JSON-able dump: every entry (tuples stringified) plus
        accounting — what serve.py reports and the autotuner will read."""
        with self._lock:
            entries = {
                k: {
                    **{f: v for f, v in e.items() if f != "op_scopes"},
                    "meta": {
                        mk: (list(mv) if isinstance(mv, tuple) else mv)
                        for mk, mv in (e.get("meta") or {}).items()
                    },
                }
                for k, e in self._entries.items()
            }
        return {"enabled": self.enabled, "entries": entries}

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()


def build_and_record(
    ledger: CostLedger, hub, jitfn, args: tuple, key: str, *,
    backend: Optional[str], **meta,
):
    """The one way a chokepoint builds an executable
    (``ShapeCachedForward._instrument``, ``training/loop._compile_step``):
    ``lower`` and ``compile`` as their two start-up phases on ``hub``
    (``utils/profiling.timed_build``), the executable's costs and phases
    into ``ledger``, and the phases into the process's start-up record
    with the compile listener's totals as they stand now, the product
    sites' summary, what the program's checkpoint policy saved by name,
    the form each level of its ``volume`` lookup took and which ``Conv2d``
    sites took another form than ``conv_general_dilated``
    (``observability/startup.py``). Returns the executable."""
    from raft_ncup_tpu.utils.profiling import compile_meter, timed_build

    kind = str(meta.get("kind", "custom"))
    sites.reset_product_sites()  # the lowering below traces the program
    remat.reset_saved_residuals()
    corr.reset_contract_forms()
    layers.reset_conv_forms()
    compiled, phases = timed_build(hub, jitfn, args, key=key, kind=kind)
    traced = sites.product_sites()
    saved = remat.saved_residuals()
    forms = corr.contract_forms()
    reformed = {f: s for f, s in layers.conv_forms().items() if f != "conv"}
    entry = ledger.record_compiled(
        key, compiled, backend=backend, phases=phases, **meta
    )
    if entry is not None:
        entry["product_sites"] = traced
    get_startup_record().program(
        key, kind, trace_lower_s=phases["trace_lower_s"],
        compile_s=phases["compile_s"], cache=phases["cache"],
        probe_s=None if entry is None else entry["probe_ms"] / 1e3,
        process=compile_meter().totals(),
        precision=sites.summarize_sites(traced, str(meta.get("policy", "unknown"))),
        # of a program that kept something across a checkpoint (the
        # training step): how many values under each name
        saved_residuals=saved if any(saved.values()) else None,
        # of a program that looks a materialised pyramid up: the form
        # and stored dtype of each level (``ops/corr.py::contract_form``)
        contract_forms=forms or None,
        # of a program with thin ``Conv2d`` sites: the module paths that
        # were folded or phased (``nn/layers.py::conv_form``)
        conv_forms=reformed if any(reformed.values()) else None,
    )
    return compiled


def first_run(ledger: CostLedger, hub, compiled, args: tuple, key: str, kind: str):
    """The first call of the executable :func:`build_and_record` banked
    under ``key``, as the start-up phase ``startup_first_run`` on ``hub``,
    banked beside the build's phases. It ends where the call returns (the
    dispatch; nothing here waits for the device and no wait is added):
    whoever called already waits for or hands on the result, which is
    returned."""
    with StartupPhase(hub, "startup_first_run", key=key, kind=kind) as phase:
        out = compiled(*args)
    ledger.record_first_run(key, phase.seconds * 1e3)
    get_startup_record().first_run(key, phase.seconds)
    return out


_default_lock = threading.Lock()
_default: Optional[CostLedger] = None


def get_cost_ledger() -> CostLedger:
    """The process-wide default ledger (created on first use; honors
    ``RAFT_NCUP_COST_LEDGER=0``)."""
    global _default
    with _default_lock:
        if _default is None:
            _default = CostLedger()
        return _default


def set_cost_ledger(ledger: Optional[CostLedger]) -> Optional[CostLedger]:
    """Swap the process default (bench/test isolation); returns the
    previous ledger."""
    global _default
    with _default_lock:
        prev, _default = _default, ledger
        return prev
