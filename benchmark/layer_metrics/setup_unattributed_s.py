"""``setup_unattributed_s``: the run's ``setup_s`` less the five phases the
program's start-up report names (trace + lower, compile-or-load, first run,
train state, first input pipeline open; a phase the cell does not have counts
0): interpreter and ``jax`` imports, the TPU client's start, the benchmark's
own reference weights and pool, the cost probe of each executable, and the
device's execution of the warm-up batch or steps. By construction the
``setup_*_s`` metrics a cell reports add up to its ``setup_s``. ``None``
where the program has no such report."""

import os

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def read(run: dict):
    shared = harness.load_module(os.path.join(HERE, "setup_trace_lower_s.py"))
    report = shared.startup()
    if report is None:
        return None
    named = sum(
        shared.program_seconds(f) for f in ("trace_lower_s", "compile_s", "first_run_s")
    ) + sum(report["phases"].get(p) or 0.0 for p in ("weights_s", "input_start_s"))
    return run["setup"]["setup_s"] - named
