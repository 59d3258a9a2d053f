"""``setup_cache_miss_programs``: programs the persistent compilation cache
did not hold (and so were compiled and written) from the installation of the
program's compile listener to the end of set-up's last executable build:
``startup_report()["process"]["cache_misses"]``. 0 on a warm run; what "which
step recompiled" reads (the executable's own verdict is its ``cache`` in the
report's ``programs``). The listener is process-wide, so the count holds the
benchmark's own programs compiled in between too. ``None`` where the program
has no such report."""

import os

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def read(run: dict):
    shared = harness.load_module(os.path.join(HERE, "setup_trace_lower_s.py"))
    report = shared.startup()
    return None if report is None else report["process"].get("cache_misses")
