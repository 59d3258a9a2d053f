"""The training cell's own tests, on the CPU at a toy size: the reference
with loss, gradients and AdamW against the program's step, and the driver
``train_steps`` through ``harness.run_cell(..., require_tpu=False)``: sound,
broken where the step is built, at a lower precision, traced. Nothing here is
a speed."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, flops_train, harness, trace_reduce, traffic_gen  # noqa: E402
from benchmark.reference.raft_train import TrainReference, onecycle_lr  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "train_sintel_nc"
CONFIG = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel-ft.json"))
TOY_TRAIN = {"batch_size": 2, "image_size": [64, 96], "iters": 2}
TOY_TRAFFIC = {"native_hw": [92, 128], "pool": 4, "num_workers": 1}
# CPU float32 against the reference: 1e-7 / 5e-5 / 2e-3 (the feature
# encoder's small gradient) / 1e-7; bf16_train reads 1e-3 to 1e-1.
TOY_LIMITS = {"loss_rel_gap": 1e-5, "grad_rel_gap": 1e-3,
              "grad_rel_gap_worst_module": 2e-2, "loss_after_steps_rel_gap": 1e-5}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TRAIN_METRICS = {"train_device_ms_per_step", "device_idle_pct.train",
                 "train_input_wait_ms_per_step", "train_dispatch_p50_ms"}


def toy_tree(tmp_path, precision: str | None = None) -> str:
    """A checkout-like tree whose one cell ``toy`` is ``train_sintel_nc`` at a
    toy size: new configuration, traffic and limits files beside the real
    ones, found by name."""
    root = str(tmp_path / "tree")
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    base = os.path.join(root, "benchmark")
    config = json.loads(json.dumps(CONFIG))
    config["train"].update(TOY_TRAIN)
    if precision:
        config["model"]["precision"] = precision
    traffic = harness.load_json(os.path.join(base, "traffic", "train_sintel_ft.json"))
    traffic.update(TOY_TRAFFIC)
    for sub, body in (("configs", config), ("traffic", traffic), ("limits", {"limits": TOY_LIMITS})):
        with open(os.path.join(base, sub, "toy.json"), "w") as f:
            json.dump(body, f)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "toy", "file": "benchmark/configs/toy.json"})
    bench["workloads"] = [{"name": "toy", "config": "toy", "traffic": "toy", "chips": 1, "why": "toy"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["toy"] if CELL in m["workloads"] else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def drive(root: str, trace: int = 0) -> dict:
    return harness.run_cell(
        "toy", 2**31 + 7, 0.5, trace, t_start=time.perf_counter(), root=root,
        require_tpu=False,
    )


@pytest.fixture()
def fresh_step(monkeypatch):
    """A step built in this test alone: the program keeps its jitted steps
    and optimizer transforms per configuration."""
    from raft_ncup_tpu.parallel import step
    from raft_ncup_tpu.training import optim

    monkeypatch.setattr(step, "_STEP_CACHE", {})
    monkeypatch.setattr(optim, "_TX_CACHE", {})
    return step, optim


# ----------------------------------------------------------- BENCHMARK.json


def test_the_cell_and_its_files_are_declared():
    cell = harness.Cell(ROOT, BENCH, CELL, 1)
    assert cell.workload["chips"] == 1 and cell.traffic["driver"] == "train_steps"
    assert set(TOY_LIMITS) <= set(cell.limits)
    assert {m["name"] for m in harness.metrics_of(BENCH["end_to_end"], CELL)} == {"pairs_per_s", "setup_s"}
    per_layer = {m["name"] for m in harness.metrics_of(BENCH["per_layer"], CELL)}
    assert per_layer == TRAIN_METRICS | {"compile_s"}
    inference = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel.json"))
    assert CONFIG["model"] == inference["model"] and CONFIG["runtime"] == inference["runtime"]
    t = CONFIG["train"]
    assert (t["batch_size"], t["image_size"], t["iters"], t["gamma"]) == (6, [368, 768], 12, 0.85)
    assert (t["lr"], t["wdecay"], t["epsilon"], t["clip"]) == (1.25e-4, 5e-5, 1e-8, 1.0)
    assert t["freeze_bn"] is True and t["freeze_raft"] is False and CONFIG["reduced"] == []
    assert cell.traffic["pool"] == 32 and cell.traffic["native_hw"] == [436, 1024]


# ------------------------------------------------------------ the reference


def test_schedule_is_torchs_onecycle():
    total, lr = 50100, 1.25e-4
    assert onecycle_lr(0, lr, total) == pytest.approx(lr / 25)
    warm_end = 0.05 * total - 1
    assert onecycle_lr(int(warm_end), lr, total) == pytest.approx(lr, rel=1e-3)
    assert onecycle_lr(total - 1, lr, total) == pytest.approx(lr / 25 / 1e4)
    assert onecycle_lr(1, lr, total) > onecycle_lr(0, lr, total)


def test_reference_step_agrees_with_the_programs(fresh_step):
    """Loss, every gradient leaf, and parameters and loss after two
    optimizer steps: program (``make_train_step``) against the reference,
    96x128, batch 2, 3 iterations, seeded weights."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.program import build_model
    from raft_ncup_tpu.config import TrainConfig
    from raft_ncup_tpu.parallel.step import make_train_step
    from raft_ncup_tpu.training.state import create_train_state

    train = {**CONFIG["train"], "batch_size": 2, "image_size": [96, 128], "iters": 3}
    ref = TrainReference(CONFIG["model"], train)
    variables = ref.ref.init_variables(2**31 + 11)
    rng = np.random.default_rng(5)
    pairs = [traffic_gen.make_pair(rng, (96, 128), 6.0) for _ in range(2)]
    batch = {k: np.stack([p[k] for p in pairs]) for k in ("image1", "image2", "flow")}
    batch["valid"] = np.ones((2, 96, 128), np.float32)
    batch["valid"][0, :10] = 0.0
    batch["flow"][1, :4, :4] = 500.0  # over max_flow: masked out
    want = ref.steps(variables, batch, 2)

    model = build_model(CONFIG["model"])
    cfg = TrainConfig(stage="sintel", batch_size=2, image_size=(96, 128), iters=3,
                      lr=train["lr"], gamma=train["gamma"], num_steps=train["num_steps"])
    _, state = create_train_state(jax.random.PRNGKey(0), model.cfg, cfg, variables=variables)
    step = make_train_step(model, cfg)
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        losses = []
        for k in range(3):
            if k == 2:
                params_after_2 = jax.tree.map(np.asarray, state.params)
            state, metrics = step(state, dev, jax.random.PRNGKey(k))
            losses.append(float(metrics["loss"]))
            if k == 0:
                # AdamW's first moment after one step from zero is 0.1 * g
                mu = optax.tree_utils.tree_get(state.opt_state, "mu")
                clipped = jax.tree.map(lambda m: np.asarray(m) / 0.1, mu)
                norm = float(metrics["grad_norm"])
    assert losses == pytest.approx(want["losses"], rel=2e-6)
    assert norm == pytest.approx(want["grad_norm"], rel=1e-5)
    scale = max(float(jnp.max(jnp.abs(g))) for g in jax.tree.leaves(want["clipped"]))
    got = jax.tree_util.tree_leaves_with_path(clipped)
    for (path, a), b in zip(got, jax.tree.leaves(want["clipped"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=2e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    # Two updates of ~lr * sign(g) each: an element whose gradient is near
    # zero may flip, so the updates are compared over the whole tree.
    num = den = 0.0
    for a, b, p0 in zip(jax.tree.leaves(params_after_2), jax.tree.leaves(want["params"]),
                        jax.tree.leaves(variables["params"])):
        num += float(np.sum((a - np.asarray(b)) ** 2))
        den += float(np.sum((np.asarray(b) - np.asarray(p0)) ** 2))
    assert den > 0 and (num / den) ** 0.5 < 0.02


def test_train_flops_are_three_forwards_and_the_recomputed_part():
    f = flops_train.train_step_flops(CONFIG["model"], 6, 368, 768, 12)
    fwd = flops.forward_flops(CONFIG["model"], 6, 368, 768, 12, upsample_every_iteration=True)
    assert f["analytic_model_flops_per_step"] == 3 * fwd
    assert 3 * fwd < f["analytic_executed_flops_per_step"] < 4 * fwd


# --------------------------------------------- the driver, through a run


def test_toy_run_is_correct_and_has_the_contract_keys(tmp_path, capsys):
    res = drive(toy_tree(tmp_path))
    assert set(res) == RESULT_KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"pairs_per_s", "setup_s"}
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines() if x.startswith("{")]
    assert lines[-1] == res
    window = next(x for x in lines if x.get("phase") == "window")
    assert window["steps"] == res["attempted"] == window["report"]["train_steps_total"]
    assert window["pairs"] == 2 * window["steps"] and len(window["losses"]) == window["steps"]
    assert {"train_dispatch", "train_throttle_wait", "input_wait"} <= set(window["report"]["stages"])
    compared = {x["check"]: x for x in lines if "check" in x}
    assert set(TOY_LIMITS) | {"compile_events_in_window", "failed",
                              "window_steps_vs_counter_gap"} <= set(compared)
    assert all(c["ok"] for c in compared.values())


@pytest.mark.parametrize("fault", ["wrong_gamma", "skipped_clip"])
def test_broken_timed_path_is_not_correct(tmp_path, fault, fresh_step, monkeypatch):
    """The step altered where it is built: the loss's gamma, or the clip
    taken out of the optimizer. The rest of the run is untouched."""
    import optax

    step, optim = fresh_step
    if fault == "wrong_gamma":
        sound = step.sequence_loss
        monkeypatch.setattr(
            step, "sequence_loss", lambda p, f, v, gamma, m: sound(p, f, v, 0.8, m)
        )
    else:
        monkeypatch.setattr(optim.optax, "clip_by_global_norm", lambda c: optax.identity())
    res = drive(toy_tree(tmp_path))
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] >= 1


def test_lower_precision_is_not_correct(tmp_path):
    """The program's own ``bf16_train`` preset through a configuration file.
    The cell's control on the chip is the reference at ``high``, which a CPU
    computes in float32; its chip readings are in PERF.md section 2."""
    res = drive(toy_tree(tmp_path, precision="bf16_train"))
    assert res["correct"] is False and res["failed"] == 0


def test_control_reads_the_numbers_the_check_compares(tmp_path):
    root = toy_tree(tmp_path)
    cell = harness.Cell(root, harness.load_json(os.path.join(root, "BENCHMARK.json")), "toy", 2**31 + 7)
    rows = cell.driver.control(cell)
    assert {r["check"] for r in rows} == set(TOY_LIMITS)
    assert all(r["value"] == 0.0 and r["ok"] for r in rows)  # both precisions are float32 here


def test_traced_run_reports_the_four_training_metrics(tmp_path, monkeypatch):
    monkeypatch.setattr(
        trace_reduce, "reduce_trace_dir",
        lambda d: {"busy_s": 0.9, "window_s": 1.0, "layout": {},
                   "device_ops": [["fusion.1", 0.4]], "idle_gaps": [["bench.window", 0.1]]},
    )
    res = drive(toy_tree(tmp_path), trace=1)
    assert set(res["metrics"]) == TRAIN_METRICS | {"compile_s"}
    steps = res["attempted"]
    assert res["metrics"]["train_device_ms_per_step"]["value"] == pytest.approx(900.0 / steps)
    assert res["metrics"]["device_idle_pct.train"]["value"] == pytest.approx(10.0)
    assert res["metrics"]["train_dispatch_p50_ms"]["value"] > 0
    assert res["metrics"]["train_input_wait_ms_per_step"]["value"] >= 0


@pytest.mark.parametrize("name", sorted(TRAIN_METRICS))
def test_readers_give_nothing_on_a_run_without_their_spans(name):
    """A program that lacks the spans and counters (the parent of PR 26), or
    a run that is not traced: ``None``, no exception."""
    reader = harness.load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    bare = {"window": {"pairs": 12}, "setup": {"compile_s": 1.0}, "report": {}}
    assert reader.read(bare) is None
    assert reader.read({**bare, "report": {"stages": {}, "train_steps_total": 0}}) is None
