"""``setup_first_run_s``: seconds of set-up in the first call of each fresh
executable, from the call to its return (the dispatch: argument transfer,
first allocations; the program adds no wait for the device): the summed
``startup_first_run`` phase over the program's start-up report. ``None``
where the program has no such report."""

import os

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def read(run: dict):
    shared = harness.load_module(os.path.join(HERE, "setup_trace_lower_s.py"))
    return shared.program_seconds("first_run_s")
