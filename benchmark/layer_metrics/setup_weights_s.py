"""``setup_weights_s``: seconds from the caller's weights to the train state
on the device (state build, optimizer init, restore, commit): the
``startup_weights`` phase of ``open_train_run`` in the program's start-up
report. ``None`` where the program has no such report or opened no run."""

import os

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def read(run: dict):
    shared = harness.load_module(os.path.join(HERE, "setup_trace_lower_s.py"))
    return shared.phase_seconds("weights_s")
