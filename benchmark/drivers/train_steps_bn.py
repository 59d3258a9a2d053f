"""Driver ``train_steps_bn``: ``train_steps`` for a recipe whose BatchNorm
layers TRAIN (``train.py --stage chairs``: upstream skips ``freeze_bn()`` for
this stage alone), so the step normalises by the batch's own statistics,
differentiates through them, and hands the running statistics on as state.
Set-up, the window, its counter rows and ``close`` are ``train_steps``'s own
functions. What differs is the reference ``correct`` holds the timed step to,
``benchmark/reference/raft_train_bn.py`` (batch statistics over the whole
batch; ``raft_train.py`` knows running statistics only), and three rows more:

- ``train_steps``'s four gaps (``loss_rel_gap``, ``grad_rel_gap``,
  ``grad_rel_gap_worst_module``, ``loss_after_steps_rel_gap``), against the
  new reference, from the seed's weights and the window's first batch through
  the TIMED executable.
- ``bn_running_stats_rel_gap``: relative L2 gap over every running mean and
  variance after ``check_steps`` calls of the timed step. A step that trains
  on batch statistics and drops the running ones before the state is updated
  is invisible to the four rows above: this row is why.
- ``bn_layers_training_gap``: the program's gauge ``train_bn_layers_training``
  (what the traced step says of itself) against the reference's count of
  BatchNorm layers; ``bn_stat_updates_gap``: the counter
  ``train_bn_stat_updates_total`` of the window against layers x steps
  dispatched. Both 0.

A program without the counter (a parent of PR 49) is refused at once, before
anything compiles.

``readings.py``: the program's rows without a window (the counter's row needs
one and is left out); ``--control``: the reference at the configuration's
control precision (``high.<row>``) and the two controls of
``raft_train_bn.CONTROLS`` (``bn_frozen.<row>``, ``stats_not_carried.<row>``)
in the program's place; ``--model-precision one_pass``: the float32 program at
the TPU's default matmul precision in the program's place.

Adding such a cell, as files and entries only: what ``train_steps`` lists,
with ``traffic/<traffic>.json`` naming this driver, ``train.freeze_bn: false``
in the configuration, ``control.drop`` naming the controls, the three rows
above in ``limits/<workload>.json``, and the cell's name in the ``workloads``
of ``train_bn_stat_layers``.
"""

from __future__ import annotations

import contextlib

import jax

from benchmark.drivers import train_steps as base
from benchmark.harness import NoResult, compared, emit
from benchmark.reference.raft_train_bn import CONTROLS, reference_for, stats_rel_gap

close = base.close
ONE_PASS = "one_pass"  # readings.py --model-precision one_pass


def _refuse_without_counter() -> None:
    from raft_ncup_tpu.parallel import step

    if not hasattr(step, "bn_layer_count"):
        raise NoResult(
            "this program does not count the BatchNorm layers its step trains "
            "(raft_ncup_tpu/parallel/step.py::bn_layer_count, the counter "
            "train_bn_stat_updates_total): the cell's compared rows cannot be read"
        )


def _reference(cell, precision: str = "highest"):
    return reference_for(cell.config["model"], cell.config["train"], precision)


def _with_reference(state: dict) -> dict:
    state["reference"] = _reference(state["cell"])
    return state


def setup(cell) -> dict:
    _refuse_without_counter()
    return _with_reference(base.setup(cell))


def run(state, seconds: float) -> dict:
    from raft_ncup_tpu.observability import get_telemetry

    window = base.run(state, seconds)
    hub = get_telemetry()  # the hub reads a gauge's value as it reads a counter's
    for name in ("train_bn_stat_updates_total", "train_bn_layers_training"):
        window["report"][name] = hub.counter_value(name)
    return window


# ----------------------------------------------------------------- the step


class _RecordingRun:
    """The run, keeping the running statistics each call of its step leaves
    (read at once: the next call donates the state's buffers)."""

    def __init__(self, run):
        self.run, self.batch_stats = run, []

    def __getattr__(self, name):
        return getattr(self.run, name)

    def step(self, state, batch, rng):
        state, metrics = self.run.step(state, batch, rng)
        self.batch_stats.append(jax.device_get(state.batch_stats))
        return state, metrics


def _program_steps(state, batch: dict, n_steps: int) -> dict:
    """``train_steps._program_steps`` (``n_steps + 1`` calls of the timed
    step on ``batch`` from the seed's weights with fresh moments), with the
    running statistics the state holds after ``n_steps`` of them and what the
    traced step says of the layers it trains."""
    run = _RecordingRun(state["run"])
    out = base._program_steps({**state, "run": run}, batch, n_steps)
    out["batch_stats"] = run.batch_stats[n_steps - 1]
    out["bn_layers"] = int(run.step_fn.report["bn_layers_training"])
    return out


def _rows(cell, got: dict, ref: dict, prefix: str = "") -> list:
    """The four gaps of ``train_steps`` and the two rows every reading has:
    the running statistics' gap and the count of layers that train."""
    rows = base._compare(cell, got, ref)
    stats = stats_rel_gap(got["batch_stats"], ref["batch_stats"])
    layers = abs(got["bn_layers"] - ref["bn_layers"])
    emit({"phase": "batch_norm", "bn_running_stats_rel_gap": stats,
          "bn_layers_training": got["bn_layers"], "reference_bn_layers": ref["bn_layers"]})
    rows += [
        compared("bn_running_stats_rel_gap", stats, cell.limit("bn_running_stats_rel_gap")),
        compared("bn_layers_training_gap", layers, cell.limit("bn_layers_training_gap")),
    ]
    return [{**r, "check": prefix + r["check"]} for r in rows]


def check(state, window: dict) -> list:
    cell, report = state["cell"], window["report"]
    batch, n = base._check_batch(state), int(cell.traffic["check_steps"])
    ref = state["reference"].steps(state["variables"], batch, n)
    got = _program_steps(state, batch, n)
    got["bn_layers"] = int(report["train_bn_layers_training"])  # the window's own gauge
    return [
        compared("window_steps_vs_counter_gap",
                 abs(window["steps"] - report["train_steps_total"]), 0),
        compared("window_pairs_vs_counter_gap",
                 abs(window["pairs"] - report["train_pairs_total"]), 0),
        *_rows(cell, got, ref),
        compared("bn_stat_updates_gap",
                 abs(report["train_bn_stat_updates_total"] - ref["bn_layers"] * window["steps"]),
                 cell.limit("bn_stat_updates_gap")),
    ]


def reading(cell, seconds: float) -> list:
    """The program's reading for ``readings.py``: the check's rows without a
    window. With ``--model-precision one_pass`` the float32 program at jax's
    default matmul precision stands in the program's place."""
    one_pass = cell.config["model"]["precision"] == ONE_PASS
    if one_pass:
        cell.config["model"]["precision"] = "f32"
    precision = jax.default_matmul_precision("default") if one_pass else contextlib.nullcontext()
    _refuse_without_counter()
    state = _with_reference(base._build(cell))
    try:
        batch, n = base._check_batch(state), int(cell.traffic["check_steps"])
        with precision:
            got = _program_steps(state, batch, n)
        return _rows(cell, got, state["reference"].steps(state["variables"], batch, n))
    finally:
        close(state)


def control(cell) -> list:
    """Every control in the program's place, on the inputs ``check`` takes:
    the reference at the configuration's control precision, then the
    reference with one statement about BatchNorm dropped."""
    inputs = base._inputs(cell)
    drops = list(cell.config["control"]["drop"])
    for drop in drops:
        if drop not in CONTROLS:
            raise NoResult(f"the reference has no control {drop!r}: {CONTROLS}")
    variables = inputs["variables"]
    batch, n = base._check_batch(inputs), int(cell.traffic["check_steps"])
    reference = _reference(cell)
    ref = reference.steps(variables, batch, n)
    low = cell.config["control"]["reference_precision"]
    rows = _rows(cell, _reference(cell, precision=low).steps(variables, batch, n), ref, f"{low}.")
    for drop in drops:
        rows += _rows(cell, reference.with_control(drop).steps(variables, batch, n), ref, f"{drop}.")
    return rows
