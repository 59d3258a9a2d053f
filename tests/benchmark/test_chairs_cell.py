"""The chairs-stage cell's own tests (PR 49), on the CPU at a toy size: the
reference with BatchNorm trained against torch and against the program's step,
its two controls, the driver ``train_steps_bn`` through
``harness.run_cell(..., require_tpu=False)`` (sound, broken three ways,
traced), the declarations in ``BENCHMARK.json`` and the one new reader.
Nothing here is a speed."""

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
from benchmark.reference import raft_train_bn  # noqa: E402
from benchmark.reference.raft import Scope  # noqa: E402
from benchmark.reference.raft_train import global_norm  # noqa: E402
from benchmark.reference.raft_train_bn import stats_rel_gap  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "train_chairs_raft"
CONFIG = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft-chairs.json"))
SINTEL = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft-sintel.json"))
SINTEL_FT = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel-ft.json"))
LIMITS = harness.load_json(os.path.join(ROOT, "benchmark/limits", CELL + ".json"))
BN_LAYERS = 15  # cnet: the stem's, two a residual block (6 blocks), one a strided shortcut (2)
TOY_TRAIN = {"batch_size": 2, "image_size": [64, 96], "iters": 2}
TOY_TRAFFIC = {"native_hw": [80, 112], "pool": 4, "num_workers": 1}
# CPU float32 against the reference at this size: 2e-7 / 1e-3 / 5e-3 (the
# encoders' small gradients) / 3e-6 / 4e-6; the controls read 2e-2 and more.
TOY_LIMITS = {"loss_rel_gap": 1e-5, "grad_rel_gap": 5e-3, "grad_rel_gap_worst_module": 3e-2,
              "loss_after_steps_rel_gap": 1e-4, "bn_running_stats_rel_gap": 1e-4,
              "bn_layers_training_gap": 0, "bn_stat_updates_gap": 0}
GAP_ROWS = ("loss_rel_gap", "grad_rel_gap", "grad_rel_gap_worst_module", "loss_after_steps_rel_gap")
BN_ROWS = ("bn_running_stats_rel_gap", "bn_layers_training_gap", "bn_stat_updates_gap")
TRAIN_SIX = ["compile_s", "train_device_ms_per_step", "device_idle_pct.train",
             "train_input_wait_ms_per_step", "train_dispatch_p50_ms", "train_step_mfu_pct"]
# the accepted entries of ``per_layer`` in their order (tests/benchmark/test_kitti_cell.py)
ACCEPTED = [
    "compile_s", "serve_queue_wait_p50_ms", "serve_drain_p50_ms", "device_ms_per_pair",
    "device_idle_pct.infer", "serve_pad_stage_p50_ms", "serve_dispatch_p50_ms",
    "serve_throttle_wait_p50_ms", "serve_device_wait_p50_ms", "serve_pull_p50_ms",
    "eval_input_wait_ms_per_pair", "eval_input_stage_ms_per_pair", "eval_input_h2d_ms_per_pair",
    "train_device_ms_per_step", "device_idle_pct.train", "train_input_wait_ms_per_step",
    "train_dispatch_p50_ms", "stream_queue_wait_p50_ms", "stream_pad_stage_p50_ms",
    "stream_dispatch_p50_ms", "stream_throttle_wait_p50_ms", "stream_device_wait_p50_ms",
    "stream_pull_p50_ms", "stream_cold_start_pct", "stream_padded_rows_pct",
    "corr_kernel_ms_per_pair", "corr_kernel_roofline_pct",
    "setup_trace_lower_s", "setup_program_load_s", "setup_first_run_s", "setup_input_start_s",
    "setup_cache_miss_programs", "setup_unattributed_s", "eval_pass_start_p50_ms",
    "train_step_mfu_pct", "train_f32_product_sites", "infer_mfu_pct", "infer_f32_product_sites",
    "eval_fill_rows_pct", "eval_program_builds_per_pass",
]


def toy_tree(tmp_path) -> str:
    """A checkout-like tree whose one cell ``toy`` is ``train_chairs_raft`` at
    a toy size: configuration, traffic and limits files beside the real ones."""
    root = str(tmp_path / "tree")
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    base = os.path.join(root, "benchmark")
    config = json.loads(json.dumps(CONFIG))
    config["train"].update(TOY_TRAIN)
    traffic = harness.load_json(os.path.join(base, "traffic", "train_chairs.json"))
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


def lines_of(capsys) -> list:
    return [json.loads(x) for x in capsys.readouterr().out.strip().splitlines() if x.startswith("{")]


def rows_of(lines: list) -> dict:
    return {x["check"]: x for x in lines if "check" in x}


@pytest.fixture()
def fresh_step(monkeypatch):
    """A step built in this test alone: the program keeps its jitted steps,
    their executables and optimizer transforms per configuration."""
    from raft_ncup_tpu.parallel import step
    from raft_ncup_tpu.training import loop, optim

    monkeypatch.setattr(step, "_STEP_CACHE", {})
    monkeypatch.setattr(loop, "_COMPILED", {})
    monkeypatch.setattr(optim, "_TX_CACHE", {})
    return step


def rel(a, b) -> float:
    import jax

    return float(global_norm(jax.tree.map(lambda x, y: x - y, a, b)) / global_norm(b))


# ----------------------------------------------------------- BENCHMARK.json


def test_the_cell_and_its_files_are_declared():
    cell = harness.Cell(ROOT, BENCH, CELL, 1)
    assert cell.workload == {**cell.workload, "config": "raft-chairs", "traffic": "train_chairs", "chips": 1}
    assert cell.traffic["driver"] == "train_steps_bn" and callable(cell.driver.control)
    assert set(GAP_ROWS + BN_ROWS) <= set(cell.limits)
    assert cell.limits["bn_layers_training_gap"] == 0 and cell.limits["bn_stat_updates_gap"] == 0
    assert {m["name"] for m in harness.metrics_of(BENCH["end_to_end"], CELL)} == {"pairs_per_s", "setup_s"}
    per_layer = [m["name"] for m in harness.metrics_of(BENCH["per_layer"], CELL)]
    assert per_layer == [*(n for n in ACCEPTED if n in TRAIN_SIX), "train_bn_stat_layers"]
    # widths, runtime and model are raft-sintel's, letter for letter
    assert all(CONFIG[k] == SINTEL[k] for k in ("widths", "runtime", "model"))
    t = CONFIG["train"]
    assert (t["stage"], t["batch_size"], t["image_size"], t["iters"]) == ("chairs", 10, [368, 496], 12)
    assert (t["lr"], t["wdecay"], t["epsilon"], t["clip"], t["gamma"]) == (4e-4, 1e-4, 1e-8, 1.0, 0.8)
    assert (t["num_steps"], t["max_flow"], t["add_noise"]) == (100000, 400.0, False)
    assert t["freeze_bn"] is False and t["freeze_raft"] is False and CONFIG["reduced"] == []
    other = {k: v for k, v in t["augmentation"].items() if k not in ("min_scale", "max_scale")}
    assert other == {k: v for k, v in SINTEL_FT["train"]["augmentation"].items() if k in other}
    assert (t["augmentation"]["min_scale"], t["augmentation"]["max_scale"]) == (-0.1, 1.0)
    assert tuple(CONFIG["control"]["drop"]) == raft_train_bn.CONTROLS
    assert {"weights", "data", "batch_statistics", "precision"} <= set(CONFIG["assumed"])
    entry = next(c for c in BENCH["configs"] if c["name"] == "raft-chairs")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200 and entry["reduced"] == []


def test_the_traffic_is_the_issues():
    assert harness.Cell(ROOT, BENCH, CELL, 1).traffic == {
        "driver": "train_steps_bn", "what": harness.Cell(ROOT, BENCH, CELL, 1).traffic["what"],
        "native_hw": [384, 512], "pool": 40, "max_flow_px": 12.0, "num_workers": 4,
        "prefetch": 2, "depth": 2, "warmup_steps": 2, "check_steps": 2, "trace_seconds": 10,
    }


def test_the_program_draws_the_chairs_augmentor_as_the_configuration_states_it():
    """``data/datasets.py``'s chairs branch and the configuration's block are
    one statement of the ranges."""
    import inspect

    from raft_ncup_tpu.data import datasets

    source = inspect.getsource(datasets._fetch_training_set)
    assert 'aug = dict(crop_size=crop, min_scale=-0.1, max_scale=1.0, do_flip=True)' in source


def test_the_entries_keep_their_order_among_themselves():
    """``test_kitti_cell.py::test_the_entries_keep_their_order_among_themselves``
    restated over names, with this PR's one entry after them all."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    known = ACCEPTED + ["train_bn_stat_layers"]
    assert [n for n in names if n in known] == known
    assert names[: len(known)] == known


def test_the_new_entry():
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == "train_bn_stat_layers"]
    assert m == {"name": "train_bn_stat_layers", "unit": "layers", "better": "higher",
                 "source": "program_counter", "layer": "model scopes", "moves": "pairs_per_s",
                 "workloads": [CELL]}
    assert m["layer"] in {x["layer"] for x in BENCH["per_layer"] if x is not m}


def test_the_six_lists_gained_the_cell_at_their_end_and_nothing_else():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in TRAIN_SIX:
        assert by_name[name]["workloads"][-1] == CELL and by_name[name]["workloads"].count(CELL) == 1
    assert by_name["train_step_mfu_pct"]["workloads"] == ["train_sintel_nc_bf16", CELL]
    assert by_name["train_f32_product_sites"]["workloads"] == ["train_sintel_nc_bf16"]
    for name in TRAIN_SIX[1:5]:
        assert by_name[name]["workloads"] == ["train_sintel_nc", "train_sintel_nc_bf16", CELL]


def test_the_pass_start_entry_is_what_it_was():
    """The case of ``test_startup_readers.py::test_new_entries_resolve_to_files_
    in_their_cells[eval_pass_start_p50_ms]`` that this PR's appended entry
    pushes over that test's place pin (index 33 of 41): its other assertions."""
    path = os.path.join(ROOT, "benchmark", "layer_metrics", "eval_pass_start_p50_ms.py")
    assert callable(harness.load_module(path).read)
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == "eval_pass_start_p50_ms"]
    assert m["layer"] == "entry points" and m["better"] == "lower"
    assert m["moves"] == "pairs_per_s" and m["source"] == "program_span"
    assert m["workloads"] == ["eval_sintel_nc", "eval_sintel_raft"]


def test_the_limits_lie_between_their_readings():
    """Each limit above the program's largest reading and under the smallest
    reading of the control named for the row."""
    readings = LIMITS["readings"]
    for row, controls in (("loss_rel_gap", ("bn_frozen", "control_high", "one_pass")),
                          ("grad_rel_gap", ("bn_frozen",)),
                          ("bn_running_stats_rel_gap", ("stats_not_carried", "bn_frozen"))):
        r, limit = readings[row], LIMITS["limits"][row]
        assert r["program_readings"] >= 12 and r["program_largest"] < limit
        for control in controls:
            assert len(r[control]) >= 1 and min(r[control]) > limit, (row, control)
    for row in GAP_ROWS:  # stats_not_carried is invisible to the four
        assert max(readings[row]["stats_not_carried"]) <= LIMITS["limits"][row]


def test_the_operation_count_has_the_mask_head_in_every_iteration():
    """``train_step_mfu_pct`` reads ``flops_train.py``: for ``variant: "raft"``
    the head's two convolutions are in every iteration, as a count by hand
    has them (PERF.md section 4)."""
    model, (b, h, w, it) = CONFIG["model"], (10, 368, 496, 12)
    f = flops_train.train_step_flops(model, b, h, w, it)
    fwd = flops.forward_flops(model, b, h, w, it, upsample_every_iteration=True)
    assert f["analytic_model_flops_per_step"] == 3 * fwd
    h8, w8 = h // 8, w // 8
    head = 2.0 * h8 * w8 * (9 * 128 * 256 + 256 * 576)
    assert flops.forward_flops(model, 1, h, w, it + 1) - flops.forward_flops(model, 1, h, w, it) == pytest.approx(
        flops._update_block(h8, w8, 324) + head)
    assert fwd == flops.forward_flops(model, b, h, w, it)  # nothing is added for the training forward


# ------------------------------------------------------------ the reference


def test_the_reference_is_independent_of_the_program():
    source = open(raft_train_bn.__file__).read()
    assert "import raft_ncup_tpu" not in source and "from raft_ncup_tpu" not in source
    assert "HIGHEST" in source and "UNBIASED" in source


def test_the_references_batchnorm_is_torchs():
    """Output, running statistics and the gradient through the statistics:
    ``batch_norm_train`` against ``torch.nn.BatchNorm2d`` in training mode,
    and the program's ``BatchNormTrain`` against both."""
    import jax
    import jax.numpy as jnp
    torch = pytest.importorskip("torch")

    from raft_ncup_tpu.nn.layers import Norm

    rng = np.random.default_rng(3)
    x = (rng.standard_normal((3, 5, 7, 4)) * 2.0 + 0.5).astype(np.float32)
    g = rng.standard_normal(x.shape).astype(np.float32)
    scale, bias = rng.uniform(0.5, 1.5, 4).astype(np.float32), rng.standard_normal(4).astype(np.float32)
    mean0, var0 = rng.standard_normal(4).astype(np.float32), rng.uniform(0.5, 2.0, 4).astype(np.float32)

    bn = torch.nn.BatchNorm2d(4, eps=1e-5, momentum=0.1)
    with torch.no_grad():
        bn.weight.copy_(torch.tensor(scale)); bn.bias.copy_(torch.tensor(bias))
        bn.running_mean.copy_(torch.tensor(mean0)); bn.running_var.copy_(torch.tensor(var0))
    xt = torch.tensor(x.transpose(0, 3, 1, 2), requires_grad=True)
    yt = bn.train()(xt)
    (yt * torch.tensor(g.transpose(0, 3, 1, 2))).sum().backward()
    want_y = yt.detach().numpy().transpose(0, 2, 3, 1)
    want_dx = xt.grad.numpy().transpose(0, 2, 3, 1)

    def reference(x):
        new: dict = {}
        sc = Scope({"BatchNorm_0": {"scale": jnp.asarray(scale), "bias": jnp.asarray(bias)}},
                   {"BatchNorm_0": {"mean": jnp.asarray(mean0), "var": jnp.asarray(var0)}})
        return raft_train_bn.batch_norm_train(sc, x, new), new[("BatchNorm_0",)]

    variables = {"params": {"BatchNorm_0": {"scale": scale, "bias": bias}},
                 "batch_stats": {"BatchNorm_0": {"mean": mean0, "var": var0}}}

    def program(x):
        y, mut = Norm("batch").apply(variables, x, train=True, mutable=["batch_stats"])
        return y, mut["batch_stats"]["BatchNorm_0"]

    for fn in (reference, program):
        (y, stats), dx = fn(jnp.asarray(x)), jax.grad(lambda v: jnp.sum(fn(v)[0] * g))(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-6)
        np.testing.assert_allclose(np.asarray(dx), want_dx, atol=2e-6)
        np.testing.assert_allclose(np.asarray(stats["mean"]), bn.running_mean.numpy(), rtol=1e-6, atol=1e-7)
        # n = 105: the unbiased variance stands 1% over the biased one
        np.testing.assert_allclose(np.asarray(stats["var"]), bn.running_var.numpy(), rtol=2e-6)


@pytest.fixture(scope="module")
def toy_steps():
    """Seeded weights, one batch at 64x96, batch 2, 2 iterations, and two
    optimizer steps of the reference and of its two controls on them."""
    train = {**CONFIG["train"], **TOY_TRAIN}
    ref = raft_train_bn.reference_for(CONFIG["model"], train)  # the toy cell's own: its runs find it compiled
    variables = ref.ref.init_variables(2**31 + 11)
    rng = np.random.default_rng(659)
    pairs = [traffic_gen.make_pair(rng, (64, 96), 6.0) for _ in range(2)]
    batch = {k: np.stack([p[k] for p in pairs]) for k in ("image1", "image2", "flow")}
    batch["valid"] = np.ones((2, 64, 96), np.float32)
    batch["valid"][0, :10] = 0.0
    out = {"train": train, "variables": variables, "batch": batch, "ref": ref,
           "reference": ref.steps(variables, batch, 2), "one_step": ref.steps(variables, batch, 1)}
    for control in raft_train_bn.CONTROLS:
        out[control] = ref.with_control(control).steps(variables, batch, 2)
    return out


def gaps(got: dict, ref: dict) -> dict:
    n = len(ref["losses"]) - 1
    return {
        "loss_rel_gap": abs(got["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0]),
        "grad_rel_gap": rel(got["clipped"], ref["clipped"]),
        "grad_rel_gap_worst_module": max(rel(got["clipped"][m], ref["clipped"][m]) for m in ref["clipped"]),
        "loss_after_steps_rel_gap": abs(got["losses"][n] - ref["losses"][n]) / abs(ref["losses"][n]),
        "bn_running_stats_rel_gap": stats_rel_gap(got["batch_stats"], ref["batch_stats"]),
    }


def test_the_reference_counts_fifteen_layers_and_moves_them_all(toy_steps):
    import jax

    ref, start = toy_steps["reference"], toy_steps["variables"]["batch_stats"]
    assert ref["bn_layers"] == BN_LAYERS == raft_train_bn.bn_layer_count(start)
    assert set(ref["batch_stats"]) == {"cnet"} and set(ref["clipped"]) == {"fnet", "cnet", "update_block"}
    moved = jax.tree.map(lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))), ref["batch_stats"], start)
    assert all(jax.tree.leaves(moved))
    # two updates at momentum 0.1 from variance 1: every running variance within 0.81 + 0.19 v, v > 0
    assert all(float(np.min(v)) > 0.81 for p, v in jax.tree_util.tree_leaves_with_path(ref["batch_stats"])
               if p[-1].key == "var")


def test_the_whole_batch_gradient_is_the_chain_rules(toy_steps):
    """The reference's blocks (the context encoder on the batch, the rest a
    sample at a time, the rows' cotangents through the encoder's ``vjp``)
    against ``jax.grad`` of the batch's mean loss in one piece."""
    import jax
    import jax.numpy as jnp

    ref, variables, batch = toy_steps["ref"], toy_steps["variables"], toy_steps["batch"]
    full = {k: jnp.asarray(v, jnp.float32) for k, v in batch.items()}

    def mean_loss(params):
        c, _ = ref._cnet_fn(params["cnet"], variables["batch_stats"]["cnet"], full["image1"])
        return sum(
            ref._rest_loss_fn(params, c[i : i + 1], *(full[k][i : i + 1] for k in ("image1", "image2", "flow", "valid")))
            for i in range(2)
        ) / 2

    loss, grads = jax.jit(jax.value_and_grad(mean_loss))(variables["params"])
    got_loss, got, _ = ref.loss_and_grads(variables, batch)
    assert float(got_loss) == pytest.approx(float(loss), rel=1e-6)
    # float32 sums in another order; a bias before BatchNorm has no gradient but rounding
    assert rel(got, grads) < 2e-4 and all(rel(got[m], grads[m]) < 2e-3 for m in grads)


def test_both_controls_fail_the_rows_named_for_them(toy_steps):
    ref = toy_steps["reference"]
    frozen, dropped = gaps(toy_steps["bn_frozen"], ref), gaps(toy_steps["stats_not_carried"], ref)
    assert frozen["loss_rel_gap"] > 100 * TOY_LIMITS["loss_rel_gap"]
    assert frozen["grad_rel_gap"] > 10 * TOY_LIMITS["grad_rel_gap"]
    assert toy_steps["bn_frozen"]["bn_layers"] == 0
    # batch statistics used, the running ones handed back as they came: invisible to the four
    assert all(dropped[row] == 0.0 for row in GAP_ROWS)
    assert dropped["bn_running_stats_rel_gap"] > 100 * TOY_LIMITS["bn_running_stats_rel_gap"]
    assert toy_steps["stats_not_carried"]["bn_layers"] == BN_LAYERS


@pytest.mark.parametrize("remat", [True, False])
def test_the_programs_step_agrees_with_the_reference(toy_steps, fresh_step, remat, monkeypatch):
    """Loss, clipped gradient, the running statistics after one and after two
    steps and the loss after two: ``make_train_step`` in the chairs stage
    against the reference, with the rematerialisation the step runs under and
    without it (the statistics leave the step once either way: a second
    update from the rematerialised forward would read 0.81 of the start's
    where one step leaves 0.9)."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.program import build_model
    from raft_ncup_tpu.config import TrainConfig
    from raft_ncup_tpu.models.raft import RAFT
    from raft_ncup_tpu.training.state import create_train_state

    if not remat:
        sound = RAFT.apply
        monkeypatch.setattr(RAFT, "apply", lambda self, *a, **k: sound(self, *a, **{**k, "remat": False}))
    train, want = toy_steps["train"], toy_steps["reference"]
    model = build_model(CONFIG["model"])
    cfg = TrainConfig(stage="chairs", batch_size=2, image_size=(64, 96), iters=train["iters"],
                      lr=train["lr"], gamma=train["gamma"], num_steps=train["num_steps"],
                      wdecay=train["wdecay"])
    _, state = create_train_state(jax.random.PRNGKey(0), model.cfg, cfg, variables=toy_steps["variables"])
    step = fresh_step.make_train_step(model, cfg)
    dev = {k: jnp.asarray(v) for k, v in toy_steps["batch"].items()}
    got = {"losses": [], "stats": []}
    with jax.default_matmul_precision("highest"):
        for k in range(3):
            state, metrics = step(state, dev, jax.random.PRNGKey(k))
            got["losses"].append(float(metrics["loss"]))
            got["stats"].append(jax.device_get(state.batch_stats))
            if k == 0:
                mu = optax.tree_utils.tree_get(state.opt_state, "mu")
                got["clipped"] = jax.tree.map(lambda m: np.asarray(m) / 0.1, mu)
    assert step.report == {"bn_layers_training": BN_LAYERS}
    got["batch_stats"] = got["stats"][1]
    read = gaps(got, want)
    assert all(read[row] <= TOY_LIMITS[row] for row in read), read
    # once a step: after ONE call the running variance still holds 0.9 of its start
    one = toy_steps["one_step"]["batch_stats"]
    assert stats_rel_gap(got["stats"][0], one) <= TOY_LIMITS["bn_running_stats_rel_gap"]


# --------------------------------------------- the driver, through a run


def test_a_sound_traced_run_is_correct_and_reads_fifteen_layers(tmp_path, capsys, fresh_step, monkeypatch):
    """One run, traced (the trace's reduction stubbed: a CPU has no device
    plane): ``correct``, every compared row, the window's counters, and the
    cell's per-layer metric set. The untraced result's two metrics are seen
    by the broken runs below."""
    monkeypatch.setattr(
        trace_reduce, "reduce_trace_dir",
        lambda d: {"busy_s": 0.9, "window_s": 1.0, "layout": {},
                   "device_ops": [["fusion.1", 0.4]], "idle_gaps": [["bench.window", 0.1]]},
    )
    res = drive(toy_tree(tmp_path), trace=1)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    lines = lines_of(capsys)
    assert lines[-1] == res
    window = next(x for x in lines if x.get("phase") == "window")
    report = window["report"]
    assert window["steps"] == res["attempted"] == report["train_steps_total"]
    assert report["train_bn_layers_training"] == BN_LAYERS
    assert report["train_bn_stat_updates_total"] == BN_LAYERS * window["steps"]
    compared = rows_of(lines)
    assert set(TOY_LIMITS) | {"compile_events_in_window", "failed", "window_steps_vs_counter_gap"} <= set(compared)
    assert all(c["ok"] for c in compared.values())
    # the share of the chip's peak needs a chip: the CPU has no row in peaks.json
    assert set(res["metrics"]) == (set(TRAIN_SIX) - {"train_step_mfu_pct"}) | {"train_bn_stat_layers"}
    assert res["metrics"]["train_bn_stat_layers"] == {"value": BN_LAYERS, "unit": "layers"}
    assert res["metrics"]["train_device_ms_per_step"]["value"] == pytest.approx(900.0 / res["attempted"])


@pytest.mark.parametrize("fault", ["freeze_bn_forced", "stats_dropped", "head_skipped"])
def test_a_broken_timed_path_is_not_correct(tmp_path, capsys, fault, fresh_step, monkeypatch):
    """The step altered where it is built: BatchNorm frozen in the model's
    forward, the new statistics dropped before ``apply_gradients``, the convex
    head left out of the loop (bilinear x8 in its place). The rest of the run
    is untouched."""
    from raft_ncup_tpu.models.raft import RAFT
    from raft_ncup_tpu.ops.geometry import upflow
    from raft_ncup_tpu.training.state import TrainState

    if fault == "freeze_bn_forced":
        sound = RAFT.apply
        monkeypatch.setattr(RAFT, "apply", lambda self, *a, **k: sound(self, *a, **{**k, "freeze_bn": True}))
    elif fault == "stats_dropped":
        sound = TrainState.apply_gradients
        monkeypatch.setattr(TrainState, "apply_gradients",
                            lambda self, grads, new_batch_stats=None: sound(self, grads))
    else:
        monkeypatch.setattr(RAFT, "_upsample", lambda self, run, flow_lr, net, bn_train=False: upflow(flow_lr, 8))
    res = drive(toy_tree(tmp_path))
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"pairs_per_s", "setup_s"}
    failed = {name for name, row in rows_of(lines_of(capsys)).items() if not row["ok"]}
    if fault == "freeze_bn_forced":
        assert {"loss_rel_gap", "grad_rel_gap", "bn_layers_training_gap", "bn_stat_updates_gap",
                "bn_running_stats_rel_gap"} <= failed
    elif fault == "stats_dropped":
        assert failed == {"bn_running_stats_rel_gap"}
    else:
        # (another update moves the second step's statistics too; the layers still train)
        assert {"loss_rel_gap", "grad_rel_gap"} <= failed
        assert not failed & {"bn_layers_training_gap", "bn_stat_updates_gap"}


def test_a_program_without_the_counter_is_refused_at_once(monkeypatch):
    """The parent of PR 49 under this PR's benchmark files: ``NoResult``
    before anything is built (``run.py`` then exits 2 and prints no line)."""
    from raft_ncup_tpu.parallel import step

    monkeypatch.delattr(step, "bn_layer_count")
    cell = harness.Cell(ROOT, BENCH, CELL, 1)
    with pytest.raises(harness.NoResult, match="train_bn_stat_updates_total"):
        cell.driver.setup(cell)


def test_the_reader_finds_nothing_without_the_counter_and_zero_in_a_frozen_step():
    reader = harness.load_module(os.path.join(ROOT, "benchmark", "layer_metrics", "train_bn_stat_layers.py"))
    assert reader.read({"report": {}, "window": {}, "setup": {}}) is None
    assert reader.read({"report": {"train_steps_total": 47}, "window": {}, "setup": {}}) is None
    assert reader.read({"report": {"train_bn_stat_updates_total": 0.0, "train_steps_total": 0}, "window": {}, "setup": {}}) is None
    # what ``train_sintel_nc``'s loop publishes (tests/test_train_loop.py: 0 on every frozen step)
    assert reader.read({"report": {"train_bn_stat_updates_total": 0.0, "train_steps_total": 47}, "window": {}, "setup": {}}) == 0.0
    assert reader.read({"report": {"train_bn_stat_updates_total": 795.0, "train_steps_total": 53}, "window": {}, "setup": {}}) == 15.0
