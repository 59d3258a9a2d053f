"""The mixed-precision training cell's own tests, on the CPU at a toy size:
the ``bf16_train`` step against the plain reference that states the policy
(``benchmark/reference/raft_train_mixed.py``), each control against it, the
program's tally of product sites, and the driver ``train_steps_mixed`` through
``harness.run_cell(..., require_tpu=False)``: sound, with a pin dropped, on a
program without the tally. Nothing here is a speed, and no whole program is
compiled for the chip."""

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

from benchmark import harness, traffic_gen  # noqa: E402
from benchmark.reference.raft_train import TrainReference, global_norm  # noqa: E402
from benchmark.reference.raft_train_mixed import CONTROLS, MixedTrainReference, bf  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL_LIMITS = harness.load_json(os.path.join(ROOT, "benchmark/limits/train_sintel_nc_bf16.json"))["limits"]
CELL = "train_sintel_nc_bf16"
CONFIG = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel-ft-bf16.json"))
F32_CONFIG = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel-ft.json"))
TOY_TRAIN = {"batch_size": 2, "image_size": [64, 96], "iters": 3}
TOY_TRAFFIC = {"native_hw": [92, 128], "pool": 4, "num_workers": 1}
GAPS = ("loss_rel_gap", "grad_rel_gap", "grad_rel_gap_worst_module", "loss_after_steps_rel_gap",
        "grad_rel_gap_upsampler")
TALLY_ROWS = ("pinned_sites_not_f32", "compute_sites_not_bf16", "f32_product_sites_gap")
HLO_ROWS = ("hlo_compute_products_not_bf16", "hlo_pinned_ops_narrow", "hlo_sums_not_f32")
SITE_ROWS = ("product_site_gap", "accumulate_bf16_site_gap_negated")
# CPU, 64x96, batch 2, 3 iterations, the bf16_train step against the mixed
# reference: 1.7e-4 / 1.6e-2 / 0.25 (fnet's small gradient) / 1.8e-5 on the
# seed read (the float32 reference in its place: 1.4e-5 / 1.7e-2 / 0.23 /
# 1.9e-4). Two bfloat16 computations of one policy stand as far apart as
# bfloat16 stands from float32: the rounding flips are amplified by the
# iterations over seeded random weights. The tolerances are three to four
# times those readings; what they hold is gamma, clip, AdamW and schedule
# (a wrong gamma reads 0.1 in the loss) and, by ``coords_bf16``, the
# coordinate carry. ``product_site_gap`` reads under 1e-6 on the CPU
# (3e-5 at the cell's grid) and 3.0e-3 or more for ``accumulate_bf16`` at
# every site; the limit is the cell's own, set on the chip. The pins are held
# by the counts of the lowered module and of the tally, all 0.
TOY_LIMITS = {"loss_rel_gap": 6e-4, "grad_rel_gap": 0.15,
              "grad_rel_gap_worst_module": 0.8, "loss_after_steps_rel_gap": 6e-4,
              "grad_rel_gap_upsampler": 0.4,
              "product_site_gap": None,  # the cell's own: filled in below
              "pinned_sites_not_f32": 0, "compute_sites_not_bf16": 0, "f32_product_sites_gap": 0,
              "hlo_compute_products_not_bf16": 0, "hlo_pinned_ops_narrow": 0, "hlo_sums_not_f32": 0}
TOY_LIMITS["product_site_gap"] = CELL_LIMITS["product_site_gap"]
MIXED_METRICS = {"train_step_mfu_pct", "train_f32_product_sites"}
TRAIN_METRICS = {"train_device_ms_per_step", "device_idle_pct.train",
                 "train_input_wait_ms_per_step", "train_dispatch_p50_ms", "compile_s"}


def toy_tree(tmp_path, model_precision: str = "bf16_train") -> str:
    """A checkout-like tree whose one cell ``toy`` is ``train_sintel_nc_bf16``
    at a toy size: configuration, traffic and limits files beside the real
    ones, found by name."""
    root = str(tmp_path / "tree")
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    base = os.path.join(root, "benchmark")
    config = json.loads(json.dumps(CONFIG))
    config["train"].update(TOY_TRAIN)
    config["model"]["precision"] = model_precision
    traffic = harness.load_json(os.path.join(base, "traffic", "train_sintel_ft_mixed.json"))
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
    """A step built in this test alone: the program keeps its jitted steps,
    executables and optimizer transforms per configuration."""
    from raft_ncup_tpu.parallel import step
    from raft_ncup_tpu.training import loop, optim

    monkeypatch.setattr(step, "_STEP_CACHE", {})
    monkeypatch.setattr(loop, "_COMPILED", {})
    monkeypatch.setattr(optim, "_TX_CACHE", {})
    return step


# ----------------------------------------------------------- BENCHMARK.json


def test_the_cell_and_its_files_are_declared():
    cell = harness.Cell(ROOT, BENCH, CELL, 1)
    assert cell.workload["chips"] == 1 and cell.traffic["driver"] == "train_steps_mixed"
    assert set(GAPS) | set(TALLY_ROWS) | set(HLO_ROWS) | {"product_site_gap"} <= set(cell.limits)
    assert all(cell.limits[name] == 0 for name in TALLY_ROWS + HLO_ROWS)
    assert {m["name"] for m in harness.metrics_of(BENCH["end_to_end"], CELL)} == {"pairs_per_s", "setup_s"}
    per_layer = {m["name"] for m in harness.metrics_of(BENCH["per_layer"], CELL)}
    assert per_layer == TRAIN_METRICS | MIXED_METRICS
    # the float32 cell's set is what it was
    f32 = {m["name"] for m in harness.metrics_of(BENCH["per_layer"], "train_sintel_nc")}
    assert f32 == TRAIN_METRICS
    # the recipe, letter for letter but for the precision
    assert CONFIG["train"] == F32_CONFIG["train"] and CONFIG["widths"] == F32_CONFIG["widths"]
    assert CONFIG["model"] == {**F32_CONFIG["model"], "precision": "bf16_train"}
    assert CONFIG["runtime"] == {"jax_default_matmul_precision": "highest"} and CONFIG["reduced"] == []
    f32_traffic = harness.load_json(os.path.join(ROOT, "benchmark/traffic/train_sintel_ft.json"))
    assert cell.traffic == {**f32_traffic, "driver": "train_steps_mixed"}
    assert "reference" not in CONFIG["control"]  # the float32 reference decides, always
    assert tuple(CONFIG["control"]["drop"]) == CONTROLS
    assert len(CONFIG["precision"]["points"]) == 10 and len(CONFIG["precision"]["pinned_sites"]) == 11
    assert all(p.split("/")[0] in CONFIG["precision"]["pinned_scopes"] for p in CONFIG["precision"]["pinned_sites"])
    # appended: the driver reads an entry put before those that were there as a change to them
    assert [m["name"] for m in BENCH["per_layer"]][-2:] == ["train_step_mfu_pct", "train_f32_product_sites"]
    mfu = next(m for m in BENCH["per_layer"] if m["name"] == "train_step_mfu_pct")
    assert mfu["source"] == "device_trace" and mfu["moves"] == "pairs_per_s"
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200


# ------------------------------------------------------------ the reference


def test_the_mixed_reference_is_independent_of_the_program():
    import benchmark.reference.raft_train_mixed as mixed

    source = open(mixed.__file__).read()
    assert "import raft_ncup_tpu" not in source and "from raft_ncup_tpu" not in source


def test_bf_rounds_as_a_cast_to_bfloat16_does():
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(np.random.default_rng(0).normal(size=4096) * 37.0, jnp.float32)
    want = x.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(bf(x)), np.asarray(want))
    # and its cotangent takes the same cast
    g = jax.grad(lambda v: jnp.sum(bf(v) * x))(x)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(want))


@pytest.fixture(scope="module")
def toy_steps():
    """Seeded weights and one batch at 64x96, batch 2, 3 iterations, and two
    optimizer steps of both references on them."""
    train = {**CONFIG["train"], **TOY_TRAIN}
    f32 = TrainReference(F32_CONFIG["model"], train)
    variables = f32.ref.init_variables(2**31 + 11)
    rng = np.random.default_rng(659)
    pairs = [traffic_gen.make_pair(rng, (64, 96), 6.0) for _ in range(2)]
    batch = {k: np.stack([p[k] for p in pairs]) for k in ("image1", "image2", "flow")}
    batch["valid"] = np.ones((2, 64, 96), np.float32)
    return {
        "train": train, "variables": variables, "batch": batch,
        "mixed": MixedTrainReference(CONFIG["model"], train).steps(variables, batch, 2),
        "float32": f32.steps(variables, batch, 2),
    }


def gaps(got: dict, ref: dict) -> dict:
    import jax

    def rel(a, b):
        return float(global_norm(jax.tree.map(lambda x, y: x - y, a, b)) / global_norm(b))

    n = len(ref["losses"]) - 1
    return {
        "loss_rel_gap": abs(got["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0]),
        "grad_rel_gap": rel(got["clipped"], ref["clipped"]),
        "grad_rel_gap_worst_module": max(rel(got["clipped"][m], ref["clipped"][m]) for m in ref["clipped"]),
        "grad_rel_gap_upsampler": rel(got["clipped"]["upsampler"], ref["clipped"]["upsampler"]),
        "loss_after_steps_rel_gap": abs(got["losses"][n] - ref["losses"][n]) / abs(ref["losses"][n]),
    }


def test_the_bf16_train_step_agrees_with_the_mixed_reference(toy_steps, fresh_step):
    """Loss, clipped gradient (whole tree and worst module) and the loss after
    two optimizer steps: ``make_train_step`` under ``bf16_train`` against the
    policy written out; the float32 reference stands no closer."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.program import build_model
    from raft_ncup_tpu.config import TrainConfig
    from raft_ncup_tpu.training.state import create_train_state

    train = toy_steps["train"]
    model = build_model(CONFIG["model"])
    cfg = TrainConfig(stage="sintel", batch_size=2, image_size=(64, 96), iters=3, lr=train["lr"],
                      gamma=train["gamma"], num_steps=train["num_steps"], precision="bf16_train")
    _, state = create_train_state(jax.random.PRNGKey(0), model.cfg, cfg, variables=toy_steps["variables"])
    step = fresh_step.make_train_step(model, cfg)
    dev = {k: jnp.asarray(v) for k, v in toy_steps["batch"].items()}
    got = {"losses": []}
    with jax.default_matmul_precision("highest"):
        for k in range(3):
            state, metrics = step(state, dev, jax.random.PRNGKey(k))
            got["losses"].append(float(metrics["loss"]))
            if k == 0:
                mu = optax.tree_utils.tree_get(state.opt_state, "mu")
                got["clipped"] = jax.tree.map(lambda m: m / 0.1, mu)
    # master weights, moments and the gradient they are made from stay float32
    assert {str(x.dtype) for x in jax.tree.leaves((state.params, state.opt_state))} <= {"float32", "int32"}
    to_mixed, to_f32 = gaps(got, toy_steps["mixed"]), gaps(got, toy_steps["float32"])
    for name in GAPS:
        assert to_mixed[name] <= TOY_LIMITS[name], (name, to_mixed, to_f32)
        assert to_f32[name] <= TOY_LIMITS[name], (name, to_mixed, to_f32)
    assert to_mixed["loss_rel_gap"] > 0.0 and to_f32["loss_rel_gap"] > 0.0  # bfloat16 did run


@pytest.mark.parametrize("drop", ["upsampler_bf16", "coords_bf16"])
def test_a_control_moves_what_its_statement_holds(toy_steps, drop):
    """One statement of the policy dropped: the control is another
    computation (no number reads 0), and it moves the part its statement is
    about: ``coords_bf16`` the loss by an order over the program's own gap,
    ``upsampler_bf16`` NCUP's own gradient more than any other module's."""
    import jax

    low = MixedTrainReference(CONFIG["model"], toy_steps["train"], drop=drop).steps(
        toy_steps["variables"], toy_steps["batch"], 2
    )
    g = gaps(low, toy_steps["mixed"])
    assert all(v > 0.0 for v in g.values()), g
    ref = toy_steps["mixed"]["clipped"]
    by_module = {
        m: float(global_norm(jax.tree.map(lambda a, b: a - b, low["clipped"][m], ref[m])) / global_norm(ref[m]))
        for m in ref
    }
    if drop == "coords_bf16":
        assert g["loss_rel_gap"] > TOY_LIMITS["loss_rel_gap"] or g["loss_after_steps_rel_gap"] > TOY_LIMITS["loss_after_steps_rel_gap"], g
    else:
        assert max(by_module, key=by_module.get) == "upsampler", by_module


@pytest.mark.parametrize("shape,stride", [
    ((7, 7, 3, 64), 2), ((3, 3, 128, 96), 2), ((3, 3, 96, 96), 1), ((1, 1, 64, 96), 2),
    ((1, 5, 384, 128), 1), ((5, 1, 384, 128), 1),
])
def test_the_accumulate_control_rounds_partial_sums(shape, stride):
    """``accumulate_bf16``'s product: the same sum as the policy's float32
    accumulation but for bfloat16 roundings of its partial sums (one per
    kernel row, or per quarter of the input channels of a one-row kernel):
    off by roundings, not by more, at every kernel shape and stride of the
    model. (Through a whole step it reads under the program's own gaps on
    the chip: PERF.md section 2.)"""
    import jax.numpy as jnp

    from benchmark.reference.raft_train_mixed import _product

    rng = np.random.default_rng(7)
    x = bf(jnp.asarray(rng.normal(size=(2, 16, 24, shape[2])), jnp.float32))
    w = bf(jnp.asarray(rng.normal(size=shape) / np.sqrt(np.prod(shape[:3])), jnp.float32))
    exact, low = _product(x, w, stride, False), _product(x, w, stride, True)
    assert low.shape == exact.shape == (2, 16 // stride, 24 // stride, shape[3])
    err = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert 1e-4 < err < 3e-2, err
    np.testing.assert_array_equal(np.asarray(low), np.asarray(bf(low)))  # kept in bfloat16


def test_a_control_name_the_reference_lacks_is_refused():
    with pytest.raises(ValueError):
        MixedTrainReference(CONFIG["model"], CONFIG["train"], drop="everything_bf16")
    with pytest.raises(ValueError):
        MixedTrainReference({**CONFIG["model"], "variant": "raft"}, CONFIG["train"])


# ---------------------------------------------------------------- the tally


@pytest.mark.parametrize("precision", ["bf16_train", "f32"])
def test_the_tally_reads_every_site_at_its_stated_dtype(precision):
    """Every fnet / cnet / update-block site takes bfloat16 operands (its sum
    handed out in bfloat16, or in float32 where the site asks for the
    accumulator), every NCUP and lookup site float32; under ``f32`` every
    site is float32."""
    import jax
    import jax.numpy as jnp

    from benchmark.program import build_model
    from raft_ncup_tpu.precision.sites import (
        product_sites, reset_product_sites, summarize_sites,
    )

    model = build_model({**CONFIG["model"], "precision": precision})
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), (1, 64, 96, 3)))
    img = jax.ShapeDtypeStruct((1, 64, 96, 3), jnp.float32)
    reset_product_sites()
    jax.eval_shape(
        lambda v, a, b: model.apply(v, a, b, iters=2, train=True, freeze_bn=True), variables, img, img
    )
    sites = product_sites()
    scopes = {path.split("/")[0] for path in sites}
    assert scopes == set(CONFIG["precision"]["compute_scopes"]) | set(CONFIG["precision"]["pinned_scopes"])
    assert "raft.update_block/gru/convz1/step" in sites and "raft.gru_context/gru/convz1/context" in sites
    assert "raft.upsample/interpolation_net/nconv_in" in sites and "raft.corr_lookup/level0" in sites
    narrow = "bfloat16" if precision == "bf16_train" else "float32"
    for path, site in sites.items():
        if path.split("/")[0] in CONFIG["precision"]["compute_scopes"]:
            assert site["operands"] == narrow and site["result"] in (narrow, "float32"), (path, site)
        else:
            assert site == {"operands": "float32", "result": "float32"}, (path, site)
    gates = [s for p, s in sites.items() if "/gru/conv" in p]
    assert len(gates) == 12 and all(s["result"] == "float32" for s in gates)
    assert sites["corr_pyramid/volume"]["result"] == "float32"
    summary = summarize_sites(sites, precision)
    assert summary["policy"] == precision and summary["sites_bf16"] + summary["sites_f32"] == len(sites)
    assert summary["sites_f32"] == (11 if precision == "bf16_train" else len(sites))
    reset_product_sites()
    assert product_sites() == {}


def test_the_thin_output_convolution_adds_its_taps_in_float32():
    """The flow head's 3x3 onto 2 channels under bfloat16 operands: one
    rounding of the float32 sum over taps and channels, as the reference's
    ``conv_m`` states it (the taps' planes used to be rounded one by one)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from raft_ncup_tpu.nn.layers import conv2d, conv_form

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 12, 16, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(3, 3, 128, 2)) * 0.05, jnp.bfloat16)
    assert conv_form(k.shape) == "folded_out"
    got = conv2d(x, k, ((1, 1), (1, 1)), site="test")
    want = lax.conv_general_dilated(
        x.astype(jnp.float32), k.astype(jnp.float32), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=lax.Precision.HIGHEST,
    )
    assert got.dtype == jnp.bfloat16
    flips = np.mean(np.asarray(got, np.float32) != np.asarray(bf(want)))
    assert flips < 0.01  # the two float32 sums differ in order alone
    # and it differentiates (the wide accumulator's own rule)
    gx, gk = jax.grad(lambda a, b: jnp.sum(conv2d(a, b, ((1, 1), (1, 1)), site="test").astype(jnp.float32)), (0, 1))(x, k)
    assert gx.dtype == jnp.bfloat16 and gk.dtype == jnp.bfloat16 and bool(jnp.isfinite(gk.astype(jnp.float32)).all())


# ------------------------------------------- the module, read and not asked


HLO_SAMPLE = """
HloModule jit_step

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %w = bf16[4]{0} convert(f32[4]{0} %p), metadata={op_name="jit(step)/raft.upsample/convert_element_type"}
  %m = bf16[4]{0} multiply(bf16[4]{0} %w, bf16[4]{0} %w), metadata={op_name="jit(step)/raft.upsample/mul"}
  %a = bf16[4]{0} add(%w, %w), metadata={op_name="jit(step)/transpose(jvp(raft.upsample))/add_any"}
  ROOT %r = f32[4]{0} convert(%m), metadata={op_name="jit(step)/raft.upsample/convert_element_type"}
}

ENTRY %main (x: bf16[2,8,8,4], k: bf16[3,3,4,4], y: f32[2,8,8,4]) -> f32[2,8,8,4] {
  %x = bf16[2,8,8,4]{3,2,1,0} parameter(0)
  %k = bf16[3,3,4,4]{3,2,1,0} parameter(1)
  %y = f32[2,8,8,4]{3,2,1,0} parameter(2)
  %c = bf16[2,8,8,4]{3,2,1,0} convolution(%x, %k), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(step)/transpose(jvp(raft.fnet))/jvp(raft.fnet)/Encoder/conv1/conv_general_dilated"}
  %g = f32[2,8,8,4]{3,2,1,0} convolution(bf16[2,8,8,4]{3,2,1,0} %x, bf16[3,3,4,4]{3,2,1,0} %k), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="raft.update_block/BasicUpdateBlock.step/gru/convz1/convz1._conv/conv_general_dilated"}
  %u = f32[2,8,8,4]{3,2,1,0} convolution(%y, %y), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(step)/jvp(raft.refinement)/while/body/raft.upsample/conv0/conv_general_dilated"}
  ROOT %d = f32[2,8,8,4]{3,2,1,0} dot(%y, %c), lhs_contracting_dims={3}, rhs_contracting_dims={3}, metadata={op_name="jit(step)/jvp(bxc,byc->bxy)/dot_general"}
}
"""


def test_the_hlo_reader_finds_products_and_narrow_arithmetic_by_scope():
    """Operand types from the operand's own text or from its definition in
    the same computation; the scope is the LAST one in the instruction's
    name; under a pinned scope a ``convert`` and a cotangent's ``add_any``
    are no arithmetic, a bfloat16 multiply is."""
    from benchmark import hlo_products

    scopes = ["raft.fnet", "raft.update_block", "raft.refinement", "raft.upsample"]
    found = hlo_products.products(HLO_SAMPLE, scopes)
    assert [(p["scope"], p["operands"], p["result"]) for p in found] == [
        ("raft.fnet", ["bf16", "bf16"], "bf16"),
        ("raft.update_block", ["bf16", "bf16"], "f32"),
        ("raft.upsample", ["f32", "f32"], "f32"),
        (None, ["f32", "bf16"], "f32"),
    ]
    assert hlo_products.narrow_ops(HLO_SAMPLE, ["raft.upsample", "raft.fnet"]) == {
        "raft.upsample": 1, "raft.fnet": 1,
    }


# --------------------------------------------------- one product at a time


@pytest.fixture(scope="module")
def site_setup():
    import jax

    from benchmark.program import build_model
    from benchmark.reference.raft_train_mixed import site_inputs, site_products

    f32 = TrainReference(F32_CONFIG["model"], {**CONFIG["train"], **TOY_TRAIN})
    params = jax.tree.map(lambda x: x, f32.ref.init_variables(2**31 + 13)["params"])
    inputs = site_inputs(params, 2**31 + 13, (8, 12))
    return {"params": params, "inputs": inputs, "want": site_products(params, inputs),
            "model_cfg": build_model(CONFIG["model"]).cfg}


def test_the_programs_products_are_the_policys_at_every_site(site_setup):
    """One site of every form of product, the program's own code on the
    reference's inputs: under the cell's limit at every site (on the CPU the
    two agree but for a rounding or two), and every site of the reference
    has a form of its own in the program."""
    from benchmark.drivers import train_steps_mixed as driver
    from benchmark.reference.raft_train_mixed import SITES
    from raft_ncup_tpu.nn.layers import conv_form

    got = driver._program_sites(site_setup["model_cfg"], site_setup["params"], site_setup["inputs"])
    gaps = driver._site_gaps(got, site_setup["want"])
    assert set(gaps) == set(SITES) and max(gaps.values()) <= CELL_LIMITS["product_site_gap"], gaps
    forms = {
        site: conv_form(driver.site_params(site_setup["params"], site)["kernel"].shape, (stride, stride))
        for site, (kind, stride) in SITES.items() if kind == "conv"
    }
    assert sorted(forms.values()) == ["conv", "conv", "folded_in", "folded_out"], forms


@pytest.mark.parametrize("drop", CONTROLS)
def test_the_site_row_sees_rounded_partial_sums_and_nothing_else(site_setup, drop):
    """``accumulate_bf16`` reads over the cell's limit at EVERY site; the two
    other controls leave every product as it was (their statements are not
    about products: the step's gaps hold them)."""
    from benchmark.drivers import train_steps_mixed as driver
    from benchmark.reference.raft_train_mixed import site_products

    low = site_products(site_setup["params"], site_setup["inputs"], drop=drop)
    gaps = driver._site_gaps(low, site_setup["want"])
    if drop == "accumulate_bf16":
        assert min(gaps.values()) > 3 * CELL_LIMITS["product_site_gap"], gaps
    else:
        assert max(gaps.values()) == 0.0, gaps


@pytest.mark.parametrize("program", ["thin_output_taps_in_bf16", "f32"])
def test_the_site_row_refuses_what_is_not_the_policy(site_setup, program):
    """The flow head's thin-output form as it stood before PR 37 (nine taps'
    planes rounded and added in bfloat16: the tally read bf16 in, bf16 out,
    and saw nothing), and the float32 program's products (never rounded):
    both over the limit."""
    import jax.numpy as jnp

    from benchmark.drivers import train_steps_mixed as driver
    from benchmark.program import build_model
    from raft_ncup_tpu.nn.layers import _conv_folded_out

    site = "update_block/flow_head/conv2"
    if program == "f32":
        cfg = build_model({**CONFIG["model"], "precision": "f32"}).cfg
        got = driver._program_sites(cfg, site_setup["params"], site_setup["inputs"])
    else:
        kernel = driver.site_params(site_setup["params"], site)["kernel"].astype(jnp.bfloat16)
        x = site_setup["inputs"][site].astype(jnp.bfloat16)
        got = {site: _conv_folded_out(x, kernel, ((1, 1), (1, 1))).astype(jnp.float32)}
    gaps = driver._site_gaps(got, {s: site_setup["want"][s] for s in got})
    assert min(gaps.values()) > 3 * CELL_LIMITS["product_site_gap"], gaps


# --------------------------------------------- the driver, through a run


def test_toy_run_is_correct_and_reports_the_tally(tmp_path, capsys, fresh_step, monkeypatch):
    """One traced toy run of the cell: ``correct``, every compared row, the
    window's precision report, the cell's per-layer metrics (the share of the
    chip's peak needs a chip: off the TPU the line leaves it out)."""
    from benchmark import trace_reduce

    monkeypatch.setattr(
        trace_reduce, "reduce_trace_dir",
        lambda d: {"busy_s": 0.9, "window_s": 1.0, "layout": {},
                   "device_ops": [["fusion.1", 0.4]], "idle_gaps": [["bench.window", 0.1]]},
    )
    res = drive(toy_tree(tmp_path), trace=1)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == TRAIN_METRICS | {"train_f32_product_sites"}
    assert res["metrics"]["train_f32_product_sites"] == {"value": 11, "unit": "sites"}
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines() if x.startswith("{")]
    assert lines[-1] == res
    window = next(x for x in lines if x.get("phase") == "window")
    precision = window["report"]["precision"]
    assert precision["policy"] == "bf16_train" and precision["sites_f32"] == 11
    assert precision["sites_bf16"] == len(precision["sites"]) - 11 == 52
    compared = {x["check"]: x for x in lines if "check" in x}
    assert set(GAPS) | set(TALLY_ROWS) | set(HLO_ROWS) | set(SITE_ROWS) | {
        "compile_events_in_window", "failed", "window_steps_vs_counter_gap"} <= set(compared)
    assert all(c["ok"] for c in compared.values())
    assert all(compared[name]["value"] == 0 for name in TALLY_ROWS + HLO_ROWS)
    # the module the compiler was handed says what the tally says
    hlo = next(x for x in lines if x.get("phase") == "hlo")["products"]
    assert hlo["raft.upsample: f32xf32->f32"] > 0 and hlo["None: bf16xbf16->f32"] == 1
    assert hlo["raft.update_block: bf16xbf16->f32"] == 14  # 6 gates + the flow head, forward and rematerialised
    assert not any(k.startswith("raft.corr_lookup") for k in hlo)  # the lookup multiplies and sums: no product instruction
    # the program's own report says the same beside the executable's phases
    from raft_ncup_tpu.observability import startup_report

    step = [p for p in startup_report()["programs"] if p["kind"] == "train_step"][-1]
    assert step["precision"] == {"policy": "bf16_train", "sites_bf16": 52, "sites_f32": 11}
    assert step["key"].endswith("|bf16_train")


def test_a_dropped_pin_is_not_correct(tmp_path, fresh_step, monkeypatch, capsys):
    """The lookup handed bfloat16 coordinates (the pin P7 taken out where the
    model calls it): its four levels contract in bfloat16, and a pinned site
    that ran in bfloat16 is a wrong answer, whatever the gaps read. The tally
    says so, and so does the lowered module, which nobody told."""
    import jax.numpy as jnp

    from raft_ncup_tpu.models import raft

    sound = raft.corr_lookup
    monkeypatch.setattr(
        raft, "corr_lookup",
        lambda pyramid, coords, radius: sound(
            pyramid, coords.astype(jnp.bfloat16), radius
        ).astype(coords.dtype),
    )
    res = drive(toy_tree(tmp_path))
    assert res["correct"] is False and res["failed"] == 0
    assert set(res["metrics"]) == {"pairs_per_s", "setup_s"}
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines() if x.startswith("{")]
    compared = {x["check"]: x for x in lines if "check" in x}
    assert compared["pinned_sites_not_f32"]["value"] == 4 and compared["compute_sites_not_bf16"]["ok"]
    assert compared["f32_product_sites_gap"]["value"] == 4
    assert compared["hlo_pinned_ops_narrow"]["value"] > 0 and compared["hlo_compute_products_not_bf16"]["ok"]


# ------------------------------------------------ a step broken where it is built


class BrokenRun:
    """The run's step with a fault in the timed path's place: the state's
    parameters handed back as they came (moments and count updated), or the
    batch's second half a copy of its first (the mean over half the
    samples). Everything else is the run's own."""

    def __init__(self, run, fault: str):
        self._run, self._fault = run, fault

    def __getattr__(self, name):
        return getattr(self._run, name)

    def step(self, st, dev, rng):
        import jax
        import jax.numpy as jnp

        if self._fault == "half_batch":
            half = next(iter(dev.values())).shape[0] // 2
            dev = {k: jnp.concatenate([v[:half], v[:half]]) for k, v in dev.items()}
            return self._run.step(st, dev, rng)
        kept = jax.tree.map(jnp.copy, st.params)  # the step donates its state
        new, metrics = self._run.step(st, dev, rng)
        return new.replace(params=kept), metrics


@pytest.fixture(scope="module")
def toy_cell(tmp_path_factory):
    """The toy cell's files, its float32 reference's two steps on the check's
    batch and the sound program's run, once for the cases below."""
    from benchmark.drivers import train_steps

    root = toy_tree(tmp_path_factory.mktemp("faults"))
    cell = harness.Cell(root, harness.load_json(os.path.join(root, "BENCHMARK.json")), "toy", 2**31 + 7)
    harness.setup_jax(cell, require_tpu=False)
    state = train_steps._build(cell)
    batch = train_steps._check_batch(state)
    ref = state["reference"].steps(state["variables"], batch, 2)
    yield {"cell": cell, "batch": batch, "reference_steps": ref, "state": state}
    cell.driver.close(state)


@pytest.mark.parametrize("fault,row", [
    ("sound", None), ("wrong_gamma", "loss_rel_gap"), ("skipped_clip", "grad_rel_gap"),
    ("state_unchanged", "loss_after_steps_rel_gap"), ("half_batch", "loss_rel_gap"),
])
def test_a_broken_step_is_not_correct(toy_cell, fault, row, monkeypatch):
    """The step's gaps with a training fault planted: the loss's gamma, the
    clip taken out of the optimizer (both where the step is built), an update
    that leaves the parameters where they were, the mean over half the batch
    (both around the sound run's executable). Each fails the row named, and
    the sound step none."""
    import optax

    from benchmark.drivers import train_steps
    from raft_ncup_tpu.parallel import step
    from raft_ncup_tpu.training import loop, optim

    cell, driver, state = toy_cell["cell"], toy_cell["cell"].driver, toy_cell["state"]
    rebuilt = fault in ("wrong_gamma", "skipped_clip")
    if rebuilt:
        for module, cache in ((step, "_STEP_CACHE"), (loop, "_COMPILED"), (optim, "_TX_CACHE")):
            monkeypatch.setattr(module, cache, {})
        if fault == "wrong_gamma":
            sound = step.sequence_loss
            monkeypatch.setattr(step, "sequence_loss", lambda p, f, v, gamma, m: sound(p, f, v, 0.8, m))
        else:
            monkeypatch.setattr(optim.optax, "clip_by_global_norm", lambda c: optax.identity())
        state = train_steps._build(cell)
    elif fault != "sound":
        state = {**state, "run": BrokenRun(state["run"], fault)}
    try:
        got = train_steps._program_steps(state, toy_cell["batch"], 2)
    finally:
        if rebuilt:
            driver.close(state)
    rows = {r["check"]: r for r in driver._gaps(cell, got, toy_cell["reference_steps"])}
    assert set(rows) == set(GAPS)
    failed = {name for name, r in rows.items() if not r["ok"]}
    assert failed == set() if row is None else row in failed, rows
    if fault == "state_unchanged":  # the gradient rows cannot see it: the moments were updated
        assert failed == {"loss_after_steps_rel_gap"}, rows


def test_the_float32_program_is_not_mistaken_for_it(tmp_path):
    """The float32 program's tally in the timed step's place: every compute
    site reads float32, so ``compute_sites_not_bf16`` is not 0 whatever the
    gaps read (on the chip: ``readings.py --model-precision f32``; the
    lowered module and the sites say the same: PERF.md section 2)."""
    import jax
    import jax.numpy as jnp

    from benchmark.program import build_model
    from raft_ncup_tpu.inference.costs import CostLedger, set_cost_ledger
    from raft_ncup_tpu.precision.sites import product_sites, reset_product_sites

    root = toy_tree(tmp_path, model_precision="f32")
    cell = harness.Cell(root, harness.load_json(os.path.join(root, "BENCHMARK.json")), "toy", 3)
    model = build_model(cell.config["model"])
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), (1, 64, 96, 3)))
    img = jax.ShapeDtypeStruct((1, 64, 96, 3), jnp.float32)
    reset_product_sites()
    jax.eval_shape(lambda v, a, b: model.apply(v, a, b, iters=2, train=True, freeze_bn=True), variables, img, img)
    ledger = CostLedger(enabled=True)
    ledger._entries["toy"] = {"meta": {"kind": "train_step", "policy": "f32"}, "product_sites": product_sites()}
    before = set_cost_ledger(ledger)
    try:
        report = cell.driver._precision_report(cell)
        assert report["compute_sites_not_bf16"] == 52 and report["pinned_sites_not_f32"] == 0
        assert report["sites_f32"] == 63 and report["sites_bf16"] == 0 and report["f32_product_sites_gap"] == 52
        ledger._entries.clear()
        with pytest.raises(harness.NoResult, match="no product sites"):
            cell.driver._precision_report(cell)
    finally:
        set_cost_ledger(before)


def test_a_program_without_the_tally_is_refused_at_once(tmp_path, monkeypatch):
    """The parent of PR 37 under this PR's benchmark files: no module
    ``raft_ncup_tpu.precision.sites``. The cell gives no result, before
    anything is built or compiled."""
    monkeypatch.setitem(sys.modules, "raft_ncup_tpu.precision.sites", None)
    root = toy_tree(tmp_path)
    cell = harness.Cell(root, harness.load_json(os.path.join(root, "BENCHMARK.json")), "toy", 3)
    t0 = time.perf_counter()
    with pytest.raises(harness.NoResult, match="product-site tally"):
        cell.driver.setup(cell)
    assert time.perf_counter() - t0 < 5.0


@pytest.mark.parametrize("name", sorted(MIXED_METRICS))
def test_the_new_readers_give_nothing_without_the_tally(name):
    reader = harness.load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    bare = {"window": {"pairs": 12, "steps": 2}, "setup": {"compile_s": 1.0}, "report": {}}
    assert reader.read(bare) is None
    assert reader.read({**bare, "report": {"stages": {}, "train_steps_total": 2}}) is None
    assert reader.read({**bare, "trace": {"busy_s": 1.0}}) is None


def test_the_mfu_reader_reads_the_devices_busy_time():
    """The window's share of the peak is of its seconds on the host's clock;
    the metric is of the seconds the device was busy in the traced window."""
    reader = harness.load_module(os.path.join(ROOT, "benchmark", "layer_metrics", "train_step_mfu_pct.py"))
    window = {"analytic_model_flops_utilisation_pct": 6.0, "window_s": 11.0, "steps": 16}
    assert reader.read({"window": window, "report": {}}) is None  # untraced
    assert reader.read({"window": window, "report": {}, "trace": {"busy_s": 10.0}}) == pytest.approx(6.6)


def test_control_reads_every_control_at_the_sites_and_through_a_step(tmp_path, monkeypatch):
    """``readings.py --control``'s rows: every control's site row first, then
    its step against the float32 reference. The references' steps are canned
    here; a whole control runs in the tests above."""
    import jax.numpy as jnp

    from benchmark.drivers import train_steps

    def canned(self, variables, batch, n_steps):
        off = {None: 0.0, "upsampler_bf16": 0.01, "coords_bf16": 0.1, "accumulate_bf16": 0.02}[getattr(self, "drop", None)]
        tree = {m: {"w": jnp.full((3,), 1.0 + off)} for m in ("fnet", "cnet", "update_block", "upsampler")}
        return {"losses": [2.0 + off] * (n_steps + 1), "clipped": tree, "grad_norm": 1.0}

    monkeypatch.setattr(TrainReference, "steps", canned)
    monkeypatch.setattr(train_steps, "_check_batch", lambda state: {})
    root = toy_tree(tmp_path)
    cell = harness.Cell(root, harness.load_json(os.path.join(root, "BENCHMARK.json")), "toy", 2**31 + 7)
    rows = cell.driver.control(cell)
    names = [r["check"] for r in rows]
    assert names[:3] == [f"{c}.product_site_gap" for c in CONTROLS]  # before any step
    assert set(names) == {f"{c}.{g}" for c in CONTROLS for g in GAPS + ("product_site_gap",)}
    by_name = {r["check"]: r for r in rows}
    assert by_name["coords_bf16.loss_rel_gap"]["value"] == pytest.approx(0.1 / 2.0, rel=1e-3)
    assert not by_name["accumulate_bf16.product_site_gap"]["ok"]
    assert by_name["coords_bf16.product_site_gap"]["value"] == 0.0
    cell.config["control"]["drop"] = ["everything_bf16"]
    with pytest.raises(harness.NoResult):
        cell.driver.control(cell)
