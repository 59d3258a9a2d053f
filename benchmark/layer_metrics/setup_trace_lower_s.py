"""``setup_trace_lower_s``: seconds of set-up spent tracing the program's
executables in Python and lowering them to StableHLO: the summed
``startup_trace_lower`` phase over every executable in the program's start-up
report (``raft_ncup_tpu.observability.startup_report()["programs"]``, the
program's documented operator surface). All host, paid warm and cold alike.

The report is process-wide and no window's reset touches it; it sums
everything recorded (a program built after set-up would already have failed
``compile_events_in_window``). ``None`` where the program has no such report
(the parent of the PR that added it) or no executable in it.

The seven other ``setup_*`` readers load :func:`startup` and
:func:`program_seconds` from this file."""


def startup():
    """The program's start-up report, or ``None`` where it has none."""
    try:
        from raft_ncup_tpu.observability import startup_report
    except ImportError:
        return None
    report = startup_report()
    return report if report.get("programs") else None


def program_seconds(field: str):
    """One per-executable phase summed over the report's programs (an
    executable that has not run yet counts 0), or ``None``."""
    report = startup()
    if report is None:
        return None
    return sum(p.get(field) or 0.0 for p in report["programs"])


def phase_seconds(name: str):
    """One process-level phase of the report, or ``None``."""
    report = startup()
    return None if report is None else report["phases"].get(name)


def read(run: dict):
    return program_seconds("trace_lower_s")
