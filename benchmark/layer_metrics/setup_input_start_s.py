"""``setup_input_start_s``: seconds from the construction of the process's
FIRST ``DevicePrefetcher`` to its first batch resident on the device and
queued (worker spawn, first ``input_stage``, first ``input_h2d``): the
``input_start`` phase in the program's start-up report. Every later pipeline
open is in ``eval_pass_start_p50_ms``. ``None`` where the program has no
such report or opened no input pipeline."""

import os

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def read(run: dict):
    shared = harness.load_module(os.path.join(HERE, "setup_trace_lower_s.py"))
    return shared.phase_seconds("input_start_s")
