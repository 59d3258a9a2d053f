"""Driver ``train_steps``: training steps through the program's own loop
(``raft_ncup_tpu/training/loop.py``: ``open_train_run`` builds state, the
jitted step of ``make_train_step``, ``FlowLoader`` with the training
augmentor and ``DevicePrefetcher``; ``train_steps`` dispatches, and
``train.py`` calls the same two functions), until the window is spent; the
steps in flight then finish.

Closed loop: the next step is dispatched as soon as the loop's throttle
admits it. The window counts whole steps, and the rate is the pairs of all
steps dispatched in it over the time from its start until the last of them
has finished on the device. ``attempted`` = steps dispatched, ``failed`` =
steps whose loss or gradient norm is not finite.

``check`` holds the timed step function to the plain reference
(``benchmark/reference/raft_train.py``) at the timed sizes, from the seed's
own weights and the first batch of the window's own stream (the loader is
deterministic in seed, epoch and index): three calls of the timed step on
that batch give the loss, the clipped gradient (AdamW's first moment after
one step from zero moments is ``(1 - b1) * g``) and the loss after two
optimizer steps, which carries clip, AdamW and schedule.

Adding a training cell, as files and entries only:

- ``configs/<config>.json``: ``model`` (as an inference configuration's),
  ``train`` (the recipe: stage, batch, crop, iterations, optimizer, schedule,
  clip, gamma, precision and the augmentor's parameters), ``runtime``,
  ``control``; its entry in ``BENCHMARK.json`` ``configs``.
- ``traffic/<traffic>.json`` with ``"driver": "train_steps"``: the pool
  (``pool``, ``native_hw``, ``max_flow_px``), the loader (``num_workers``,
  ``prefetch``, ``depth``), ``warmup_steps``, ``check_steps``,
  ``trace_seconds``.
- ``limits/<workload>.json`` from ``readings.py`` on the chip (program on a
  dozen seeds, ``--control`` on three or more, ``--model-precision
  bf16_train`` on one).
- the cell's name in the ``workloads`` of ``train_device_ms_per_step``,
  ``device_idle_pct.train``, ``train_input_wait_ms_per_step``,
  ``train_dispatch_p50_ms`` and ``compile_s``.
- a model the reference does not cover brings its reference beside
  ``reference/raft_train.py``; operations come from ``flops_train.py``.
"""

from __future__ import annotations

import os
import time

import jax
import numpy as np

from benchmark import flops_train, meters, traffic_gen
from benchmark.harness import ROOT, compared, emit
from benchmark.program import build_model
from benchmark.reference.raft_train import TrainReference, global_norm

B1 = 0.9  # AdamW's first-moment decay in the recipe (torch's default)


def _train_config(cell):
    from raft_ncup_tpu.config import TrainConfig

    tr = cell.config["train"]
    return TrainConfig(
        name=cell.name, stage=tr["stage"], lr=tr["lr"], num_steps=tr["num_steps"],
        batch_size=tr["batch_size"], image_size=tuple(tr["image_size"]),
        iters=tr["iters"], wdecay=tr["wdecay"], epsilon=tr["epsilon"],
        clip=tr["clip"], gamma=tr["gamma"], max_flow=tr["max_flow"],
        optimizer=tr["optimizer"], scheduler=tr["scheduler"],
        add_noise=tr["add_noise"], sum_freq=tr["sum_freq"],
        seed=cell.seed & 0x7FFFFFFF, precision=cell.config["model"]["precision"],
        checkpoint_dir=os.path.join(ROOT, ".cache", "benchmark", "train"),
    )


def _inputs(cell) -> dict:
    """What program and control share: the reference, the seed's weights,
    the augmented pool and the program's configurations."""
    from raft_ncup_tpu.config import DataConfig
    from raft_ncup_tpu.data import ArrayFlowDataset

    t, tr = cell.traffic, cell.config["train"]
    reference = TrainReference(cell.config["model"], tr)
    aug = dict(tr["augmentation"], crop_size=tuple(tr["image_size"]))
    return {
        "cell": cell, "reference": reference,
        "variables": reference.ref.init_variables(cell.seed),
        "dataset": ArrayFlowDataset(traffic_gen.make_pool(t, cell.seed), aug),
        "train_cfg": _train_config(cell),
        "data_cfg": DataConfig(
            num_workers=int(t["num_workers"]), prefetch=int(t["prefetch"]),
            device_prefetch=int(t["depth"]),
        ),
    }


def _build(cell) -> dict:
    """The inputs and the program's run, nothing dispatched yet."""
    from raft_ncup_tpu.training.logger import Logger
    from raft_ncup_tpu.training.loop import open_train_run

    state, tr = _inputs(cell), cell.config["train"]
    model, train_cfg = build_model(cell.config["model"]), state["train_cfg"]
    if (train_cfg.stage != "chairs") != tr["freeze_bn"] or model.cfg.freeze_raft != tr["freeze_raft"]:
        raise ValueError("the configuration's freeze_bn / freeze_raft are not what the program runs")
    state["run"] = open_train_run(
        model.cfg, train_cfg, state["data_cfg"], dataset=state["dataset"],
        variables=state["variables"],
    )
    state["logger"] = Logger(
        os.path.join(train_cfg.checkpoint_dir, cell.name), config=train_cfg,
        sum_freq=train_cfg.sum_freq, use_tensorboard=False,
    )
    return state


def _steps(state, stop, after_step=None) -> None:
    from raft_ncup_tpu.training.loop import train_steps

    train_steps(state["run"], stop, logger=state["logger"], after_step=after_step)


def setup(cell) -> dict:
    state = _build(cell)
    first, n = state["run"].step_i, int(cell.traffic["warmup_steps"])
    # warm-up: the program's compile (or cache load) and a few steps
    # through the very loop the window runs.
    _steps(state, lambda i: i >= first + n)
    return state


def run(state, seconds: float) -> dict:
    from raft_ncup_tpu.inference.costs import get_cost_ledger
    from raft_ncup_tpu.observability import get_telemetry

    cell, train_cfg = state["cell"], state["train_cfg"]
    hub = get_telemetry()
    hub.reset()  # the window's spans and counters alone
    scalars = []

    def after_step(step_i, metrics) -> bool:
        scalars.append((metrics["loss"], metrics["grad_norm"]))
        return False

    t0 = time.perf_counter()
    _steps(state, lambda i: time.perf_counter() - t0 >= seconds, after_step)
    window_s = time.perf_counter() - t0  # every dispatched step has finished
    steps = len(scalars)
    values = np.asarray(jax.device_get(scalars), np.float64).reshape(steps, 2)
    failed = int(np.sum(~np.isfinite(values).all(axis=1)))
    pairs = steps * train_cfg.batch_size
    wait = hub.registry.get("input_wait_ms")
    banked = get_cost_ledger().lookup(kind="train_step") or {}
    h, w = train_cfg.image_size
    info = flops_train.train_step_flops(
        cell.config["model"], train_cfg.batch_size, h, w, train_cfg.iters
    )
    device = jax.devices()[0]
    if device.platform == "tpu":
        peak = meters.load_peaks(device.device_kind)["flops_per_s"]
        for name in ("model", "executed"):
            info[f"analytic_{name}_flops_utilisation_pct"] = (
                100.0 * info[f"analytic_{name}_flops_per_step"] * steps / window_s / peak
            )
    return {
        "window_s": window_s, "attempted": steps, "failed": failed,
        "end_to_end": {"pairs_per_s": pairs / window_s},
        "steps": steps, "pairs": pairs, "generator_lateness_s": 0.0,
        "losses": values[:, 0].tolist(), "grad_norms": values[:, 1].tolist(),
        "executable_memory": banked.get("memory_stats"),
        "report": {
            "stages": hub.tracer.stage_summary(),
            "train_steps_total": hub.counter_value("train_steps_total"),
            "train_pairs_total": hub.counter_value("train_pairs_total"),
            "input_wait_ms_sum": None if wait is None else wait.sum_ms,
        },
        **info,
    }


def check(state, window: dict) -> list:
    report = window["report"]
    return [
        compared("window_steps_vs_counter_gap",
                 abs(window["steps"] - report["train_steps_total"]), 0),
        compared("window_pairs_vs_counter_gap",
                 abs(window["pairs"] - report["train_pairs_total"]), 0),
        *_gaps_to_reference(state),
    ]


def _check_batch(state) -> dict:
    """The first host batch of the window's own stream, built again (the
    loader is deterministic in seed, epoch and index)."""
    from raft_ncup_tpu.data import FlowLoader

    train_cfg = state["train_cfg"]
    stream = FlowLoader(
        state["dataset"], batch_size=train_cfg.batch_size, seed=train_cfg.seed,
        num_workers=state["data_cfg"].num_workers, prefetch=1,
    ).batches()
    try:
        return next(stream)
    finally:
        stream.close()


def _rel_gap(tree, ref_tree) -> float:
    """||tree - ref|| / ||ref|| over all leaves."""
    diff = jax.tree.map(lambda a, b: a - b, tree, ref_tree)
    return float(global_norm(diff) / global_norm(ref_tree))


def _program_steps(state, batch: dict, n_steps: int) -> dict:
    """``n_steps + 1`` calls of the timed step on ``batch`` from the seed's
    weights with fresh moments: the losses, and the clipped gradient of the
    first call read back from AdamW's first moment."""
    import optax

    from raft_ncup_tpu.parallel.multihost import device_put_batch
    from raft_ncup_tpu.training.state import create_train_state

    run, train_cfg = state["run"], state["train_cfg"]
    _, st = create_train_state(
        jax.random.PRNGKey(train_cfg.seed), run.model.cfg, train_cfg,
        variables=state["variables"],
    )
    st = jax.device_put(st, jax.devices()[0])  # committed, as the loop's is
    dev = device_put_batch(batch, None, None)
    out = {"losses": []}
    for k in range(n_steps + 1):
        rng = jax.random.fold_in(jax.random.PRNGKey(train_cfg.seed), k)
        st, metrics = run.step(st, dev, rng)
        out["losses"].append(float(metrics["loss"]))
        if k == 0:
            mu = optax.tree_utils.tree_get(st.opt_state, "mu")
            out["clipped"] = jax.tree.map(lambda m: m / (1.0 - B1), mu)
            out["grad_norm"] = float(metrics["grad_norm"])
    return out


def _compare(cell, got: dict, ref: dict) -> list:
    """The compared rows: ``got`` (the program's steps, or the control's)
    against the reference's."""
    modules = {
        top: _rel_gap(got["clipped"][top], ref["clipped"][top]) for top in ref["clipped"]
    }
    n = len(ref["losses"]) - 1
    rows = {
        "loss_rel_gap": abs(got["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0]),
        "grad_rel_gap": _rel_gap(got["clipped"], ref["clipped"]),
        "grad_rel_gap_worst_module": max(modules.values()),
        "loss_after_steps_rel_gap": abs(got["losses"][n] - ref["losses"][n]) / abs(ref["losses"][n]),
    }
    emit({
        "phase": "reference", "optimizer_steps": n,
        "losses": got["losses"], "reference_losses": ref["losses"],
        "grad_norm": got["grad_norm"], "reference_grad_norm": ref["grad_norm"],
        "grad_rel_gap_by_module": modules, **rows,
    })
    return [compared(name, value, cell.limit(name)) for name, value in rows.items()]


def _gaps_to_reference(state) -> list:
    cell = state["cell"]
    batch, n = _check_batch(state), int(cell.traffic["check_steps"])
    ref = state["reference"].steps(state["variables"], batch, n)
    return _compare(cell, _program_steps(state, batch, n), ref)


def reading(cell, seconds: float) -> list:
    """The program's reading of the numbers the limits are set from, for
    ``readings.py``: the check alone, which needs no window."""
    state = _build(cell)
    try:
        return _gaps_to_reference(state)
    finally:
        close(state)


def control(cell) -> list:
    """The control's reading: the reference at the configuration's control
    precision in the program's place, on the batch ``check`` takes."""
    state = _inputs(cell)
    batch, n = _check_batch(state), int(cell.traffic["check_steps"])
    low = TrainReference(
        cell.config["model"], cell.config["train"],
        precision=cell.config["control"]["reference_precision"],
    )
    ref = state["reference"].steps(state["variables"], batch, n)
    return _compare(cell, low.steps(state["variables"], batch, n), ref)


def close(state) -> None:
    state["run"].close()
    state["logger"].close()
