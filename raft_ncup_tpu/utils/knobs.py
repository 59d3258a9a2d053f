"""The single env-knob registry: every ``RAFT_NCUP_*`` environment
variable the repo reads is declared here ONCE — name, type,
default, one doc line — and read ONLY through the ``knob_*`` getters
below. Lint rule JGL013 (analysis/rules/jgl013_env_knobs.py) enforces
both halves statically: a bare ``os.environ`` read of a matching name
anywhere else is a finding, and so is a registered knob nobody reads.
The getters enforce the same contract at runtime by raising on names
missing from the registry.

The registry is data the tooling consumes three ways:

- the getters (runtime reads),
- JGL013, which AST-parses the ``Knob("NAME", ...)`` literal calls
  (first argument must stay a string literal — the linter cannot
  evaluate expressions, and neither should a human auditing the knob
  surface),
- :func:`catalog_markdown`, which emits the knob table docs/PERF.md
  carries (``python -m raft_ncup_tpu.utils.knobs``); a tier-1 test pins
  that every registered name appears there.

``kind`` tokens and their getter semantics:

- ``str`` / ``raw``: the env string when set, else the default
  (:func:`knob_str` / :func:`knob_raw`; ``raw`` knobs default to None).
- ``int`` / ``float``: parsed env value (:func:`knob_int` /
  :func:`knob_float`).
- ``flag``: opt-IN boolean — true only when the env value is exactly
  ``"1"`` (:func:`knob_flag`).
- ``enabled``: opt-OUT boolean — true unless the env value is exactly
  ``"0"`` (:func:`knob_enabled`).
- ``posint``: positive-int override or None meaning "auto" — unset,
  non-int, and non-positive all mean no override
  (:func:`knob_positive_int`; the correlation tuning-knob semantics
  formerly in ``ops/corr._env_int``).

Defaults that depend on runtime context (accelerator vs CPU, device
count) are passed by the call site via the getters' ``default=``
argument; the registered default column then documents the rule rather
than a literal value.

Pure stdlib, no jax: importable from ``fleet/`` and ``observability/``
(JGL010) and parseable by the analysis package without executing
anything heavier than this module.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    kind: str  # str | raw | int | float | flag | enabled | posint
    default: Optional[str]  # documented default; None = unset/auto
    doc: str


KNOBS: Tuple[Knob, ...] = (
    # ----------------------------------------------------- model / ops
    Knob("RAFT_NCUP_NCONV_IMPL", "str", "xla",
         "Normalized-convolution implementation: 'xla' or 'pallas' "
         "(falls back per shape when the kernel cannot lower)."),
    Knob("RAFT_NCUP_CORR_QUERY_BLOCK", "posint", "128",
         "Pallas correlation query-block size; smaller blocks buy band "
         "rows inside the VMEM budget (ROADMAP item 1 sweep surface)."),
    Knob("RAFT_NCUP_CORR_BAND_ROWS", "posint", None,
         "Pallas correlation band-rows override; unset = the "
         "VMEM-budget band plan decides."),
    Knob("RAFT_NCUP_CORR_ROW_CHUNK", "posint", "8",
         "Row-chunk size the on-the-fly correlation scan traces with; "
         "larger chunks amortize the scan at more peak memory."),
    Knob("RAFT_NCUP_VMEM_BYTES", "int", "16777216",
         "Per-core VMEM capacity assumed by kernel band planning."),
    Knob("RAFT_NCUP_EARLYEXIT", "flag", "0",
         "Enable in-graph per-sample early exit for converged flow in "
         "the serving forward (docs/PERF.md 'Early exit')."),
    Knob("RAFT_NCUP_EARLYEXIT_TOL", "float", "0.05",
         "Early-exit convergence tolerance: mean |flow delta| per "
         "sample in LOW-RES pixels below which a lane freezes."),
    # ------------------------------------------------- runtime drivers
    Knob("RAFT_NCUP_PLATFORM", "raw", None,
         "Force the jax platform ('cpu', 'tpu'); the --platform flag's "
         "env fallback."),
    Knob("RAFT_NCUP_CHAOS", "raw", None,
         "Deterministic fault-injection spec (resilience/chaos.py); "
         "the --chaos flag's env fallback."),
    Knob("RAFT_NCUP_TELEMETRY", "enabled", "1",
         "Process-default telemetry hub enable; '0' creates the "
         "default hub disabled."),
    Knob("RAFT_NCUP_FLIGHT_DIR", "raw", None,
         "Flight-recorder directory for the process-default telemetry "
         "hub and serve.py's --flight_dir default."),
    Knob("RAFT_NCUP_COST_LEDGER", "enabled", "1",
         "Compiled-executable cost ledger enable; '0' disables "
         "harvesting."),
    Knob("RAFT_NCUP_CPU_PEAK_FLOPS", "raw", None,
         "Override the nominal per-host CPU peak FLOP/s used for CPU "
         "MFU; unset = cores x 4.8e10."),
)


def _build_registry() -> Dict[str, Knob]:
    by_name: Dict[str, Knob] = {}
    for knob in KNOBS:
        if knob.name in by_name:
            raise ValueError(f"duplicate env knob declaration: {knob.name}")
        by_name[knob.name] = knob
    return by_name


_BY_NAME: Dict[str, Knob] = _build_registry()


def get(name: str) -> Knob:
    """The :class:`Knob` declared for ``name``; raises ``KeyError`` for
    names missing from the registry — the runtime half of JGL013."""
    try:
        return _BY_NAME[name]
    except KeyError:
        raise KeyError(
            f"unregistered env knob {name!r}: declare it in "
            "raft_ncup_tpu/utils/knobs.py (lint rule JGL013)"
        ) from None


def knob_raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """The raw env string when set; else ``default`` when given (the
    call site owns context-dependent defaults); else the registered
    default."""
    knob = get(name)
    raw = os.environ.get(name)
    if raw is not None:
        return raw
    return default if default is not None else knob.default


def knob_str(name: str, default: Optional[str] = None) -> str:
    """Like :func:`knob_raw` but for knobs that always resolve to a
    string (a registered or call-site default exists)."""
    value = knob_raw(name, default)
    if value is None:
        raise ValueError(f"env knob {name} has no value and no default")
    return value


def knob_int(name: str, default: Optional[str] = None) -> int:
    return int(knob_str(name, default))


def knob_float(name: str, default: Optional[str] = None) -> float:
    return float(knob_str(name, default))


def knob_flag(name: str) -> bool:
    """Opt-in boolean: true only when the env value is exactly '1'."""
    get(name)
    return os.environ.get(name) == "1"


def knob_enabled(name: str) -> bool:
    """Opt-out boolean: true unless the env value is exactly '0'."""
    get(name)
    return os.environ.get(name, "1") != "0"


def knob_positive_int(name: str) -> Optional[int]:
    """Positive-int override or None meaning "auto": unset, non-int,
    and non-positive values all mean "no override" (the correlation
    tuning-knob parse shared by row-chunk / query-block / band-rows)."""
    get(name)
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        return None
    return v if v > 0 else None


def catalog_markdown() -> str:
    """The knob catalog as a markdown table (the docs/PERF.md block;
    ``python -m raft_ncup_tpu.utils.knobs`` prints it)."""
    lines = [
        "| Knob | Kind | Default | Meaning |",
        "| --- | --- | --- | --- |",
    ]
    for knob in sorted(KNOBS, key=lambda k: k.name):
        default = "unset" if knob.default is None else f"`{knob.default}`"
        lines.append(
            f"| `{knob.name}` | {knob.kind} | {default} | {knob.doc} |"
        )
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    print(catalog_markdown(), end="")
