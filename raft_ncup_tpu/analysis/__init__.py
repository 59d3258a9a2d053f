"""Correctness tooling for the sync-free, recompile-free hot path.

Two layers, one invariant set:

- **graftlint** (``lint.py`` + ``rules/``): AST-based static analysis
  with JAX-specific rules JGL001-JGL007 — host syncs in traced code,
  donation-less state-carrying jits, trace-time nondeterminism, Python
  control flow on tracers, dtype hygiene in the numeric core,
  undeclared PartitionSpec axes, and swallowed exceptions in the
  fault-handling layers (resilience//training//data/). Run it with
  ``python -m raft_ncup_tpu.analysis [paths...]``; audited exceptions
  live in ``allowlist.txt``. Pure stdlib — safe on hosts with a wedged
  accelerator backend.
- **runtime guards** (``guards.py``): ``forbid_host_transfers`` /
  ``RecompileWatchdog`` / ``max_recompiles`` / ``strict_guards`` assert
  the same invariants live, on the actual train loop (pytest
  fixtures in tests/conftest.py; ``--strict_guards`` in train.py).

The linter proves the invariants statically; the guards catch what
static analysis cannot see (dispatch-time transfers, shape-drift
recompiles). docs/ANALYSIS.md documents both layers.

This module intentionally does NOT import ``guards`` (which imports
jax) at package import: the lint CLI must not initialize a backend.
"""

from __future__ import annotations

from raft_ncup_tpu.analysis.astutil import Finding  # noqa: F401
from raft_ncup_tpu.analysis.lint import (  # noqa: F401
    LintResult,
    load_allowlist,
    main,
    run_lint,
)

__all__ = ["Finding", "LintResult", "load_allowlist", "main", "run_lint"]


def __getattr__(name: str):
    # Lazy: `from raft_ncup_tpu.analysis import guards` works without the
    # lint CLI paying the jax import.
    if name == "guards":
        import importlib

        return importlib.import_module("raft_ncup_tpu.analysis.guards")
    raise AttributeError(name)
