"""``setup_program_load_s``: seconds of set-up inside ``.compile()`` of the
program's executables: the backend's compile (cold) or the persistent
cache's load (warm); the summed ``startup_compile`` phase over the program's
start-up report. A wall clock around the program's own large executables;
``compile_s`` beside it sums jax's duration events over EVERY program of the
process. ``None`` where the program has no such report."""

import os

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def read(run: dict):
    shared = harness.load_module(os.path.join(HERE, "setup_trace_lower_s.py"))
    return shared.program_seconds("compile_s")
