"""A child-process runner that keeps the child's output when it is killed.

bench.py measures in a child so that a crash or a hang leaves the parent
alive to report it; :func:`run_watchdogged` is the bounded
``subprocess`` call it uses.
"""

from __future__ import annotations

import subprocess
import sys
from typing import NamedTuple, Optional


class ChildResult(NamedTuple):
    returncode: Optional[int]  # None when killed by the watchdog
    stdout: str
    stderr: str
    timed_out: bool

    def tail(self, n: int = 12) -> str:
        """Last ``n`` lines of the child's combined output (stdout then
        stderr) for diagnostics — neither stream is dropped."""
        combined = "\n".join(s for s in (self.stdout, self.stderr) if s)
        return "\n".join(combined.strip().splitlines()[-n:])


def run_watchdogged(
    cmd: list[str],
    timeout_s: float,
    env: Optional[dict] = None,
    cwd: Optional[str] = None,
) -> ChildResult:
    """``subprocess.run(capture_output=True, timeout=...)`` loses the
    child's partial output on timeout (POSIX ``TimeoutExpired.stdout`` is
    None — verified on this interpreter), which defeats harvest-on-kill
    designs. This Popen-based variant kills the child on expiry and then
    drains the pipes, so whatever the child printed before the watchdog
    fired is preserved."""
    proc = subprocess.Popen(
        cmd,
        env=env,
        cwd=cwd,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    try:
        out, err = proc.communicate(timeout=timeout_s)
        return ChildResult(proc.returncode, out or "", err or "", False)
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            out, err = proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:  # pragma: no cover - wedged pipes
            out, err = "", ""
        return ChildResult(None, out or "", err or "", True)
