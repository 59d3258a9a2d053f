"""JGL013 — one env-knob registry, no stragglers.

Every ``RAFT_NCUP_*`` environment knob is declared exactly
once in ``raft_ncup_tpu/utils/knobs.py`` (name, kind, default, doc) and
read exclusively through its ``knob_*`` getters — the same
one-declarative-object discipline the repo applies to fleet topology
and SLOs. Three checks, all whole-program:

- a direct ``os.environ`` read (``.get``/``[]``/``os.getenv``/``in``)
  whose name matches the knob prefixes, anywhere outside ``knobs.py``
  itself, is a finding — the knob exists but dodges the registry (so it
  has no declared type, no default documentation, and the PERF.md
  catalog misses it);
- a ``knob_*`` getter call naming a knob the registry does not declare
  is a finding (the getters also raise at runtime; the rule catches it
  before anything runs);
- a registered knob that no ``knob_*`` call ever reads is a finding —
  a dead knob, or a migration that silently dropped a reader. This
  half only runs when the linted set contains BOTH the registry
  (``knobs.py``) and every driver entry point (``train.py``,
  ``serve.py`` — where knob readers outside the package live): a
  package-only lint sees the registry but not the drivers and cannot
  call a knob dead, the same scope-completeness gate JGL012 applies
  to its drift halves.

Names are resolved through module-level string constants and import
aliases (``os.environ.get(TELEMETRY_ENV)`` with ``TELEMETRY_ENV``
imported from another module still resolves); dynamic names are out of
static reach — the getters' runtime registry check covers them.
Variables under any other prefix are not knobs and stay unmanaged.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterator, List

from raft_ncup_tpu.analysis.astutil import Finding
from raft_ncup_tpu.analysis.project import ProjectIndex

RULE_ID = "JGL013"
SUMMARY = (
    "env knob read outside utils/knobs.py, unregistered knob name, or "
    "registered knob never read (whole-program)"
)

KNOB_PREFIX = re.compile(r"^RAFT_NCUP_")

# The entry points outside the package where knob readers live; the
# unread-knob half only runs when all of them are in the linted set.
DRIVER_BASENAMES = frozenset({"train.py", "serve.py"})


def _basename(path: str) -> str:
    return path.replace("\\", "/").rsplit("/", 1)[-1]


def _package_registry() -> Dict[str, None]:
    """Fallback registry: ``Knob("NAME", ...)`` declarations parsed
    from the package's own utils/knobs.py, so linting a subdirectory
    standalone still validates getter names. Empty on partial
    checkouts — silence, never a crash."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        "utils", "knobs.py",
    )
    try:
        with open(path, encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=path)
    except (OSError, SyntaxError):
        return {}
    out: Dict[str, None] = {}
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, (ast.Name, ast.Attribute))
            and (
                node.func.id if isinstance(node.func, ast.Name)
                else node.func.attr
            ) == "Knob"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            out[node.args[0].value] = None
    return out


def check_project(proj: ProjectIndex) -> Iterator[Finding]:
    decls = [
        d for d in proj.knob_decls
        if _basename(d.site.path) == "knobs.py"
    ]
    registry_in_scope = bool(decls)
    registered = {d.name for d in decls} or set(_package_registry())

    findings: List[Finding] = []

    for read in proj.env_reads:
        if read.name is None or not KNOB_PREFIX.match(read.name):
            continue
        if _basename(read.site.path) == "knobs.py":
            continue  # the registry's own getters
        findings.append(Finding(
            path=read.site.path,
            line=read.site.line,
            col=read.site.col,
            rule=RULE_ID,
            message=(
                f"direct os.environ read of knob {read.name!r} outside "
                "the registry — read it through "
                "raft_ncup_tpu.utils.knobs (knob_raw/knob_int/"
                "knob_flag/...) so the name, type and default are "
                "declared once"
            ),
            qualname=read.site.qual,
        ))

    for call in proj.knob_calls:
        if call.name is None:
            continue  # dynamic name: the getter raises at runtime
        if call.name not in registered:
            findings.append(Finding(
                path=call.site.path,
                line=call.site.line,
                col=call.site.col,
                rule=RULE_ID,
                message=(
                    f"{call.getter}({call.name!r}) names a knob the "
                    "registry does not declare — add a Knob(...) entry "
                    "to raft_ncup_tpu/utils/knobs.py"
                ),
                qualname=call.site.qual,
            ))

    basenames = {_basename(p) for p in proj.paths}
    if registry_in_scope and DRIVER_BASENAMES <= basenames:
        read_names = {c.name for c in proj.knob_calls if c.name}
        for decl in sorted(decls, key=lambda d: d.name):
            if decl.name not in read_names:
                findings.append(Finding(
                    path=decl.site.path,
                    line=decl.site.line,
                    col=decl.site.col,
                    rule=RULE_ID,
                    message=(
                        f"knob {decl.name!r} is registered but no "
                        "knob_* getter ever reads it — dead knob, or a "
                        "reader was dropped in a migration"
                    ),
                    qualname=decl.site.qual,
                ))

    yield from findings
