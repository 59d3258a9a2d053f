"""The mixed-precision evaluation cell's own tests, on the CPU at a toy size:
the ``bf16_infer`` test-mode forward against the plain reference that states
the policy (``benchmark/reference/raft_infer_mixed.py``) and against the
float32 reference, each control against the row that is said to hold it, the
program's tally of the test-mode forward, and the driver ``eval_pass_mixed``
through ``harness.run_cell(..., require_tpu=False)``: sound, the float32
program in its place, a program without the report. Nothing here is a speed,
and no whole program is compiled for the chip (that is one case of
``tests/test_tpu_aot_compile.py``)."""

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

from benchmark import flops, harness, traffic_gen  # noqa: E402
from benchmark.reference.raft import Reference  # noqa: E402
from benchmark.reference.raft_infer_mixed import (  # noqa: E402
    CONTROLS, MixedInferReference, lookup_site, lookup_site_inputs,
)

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "eval_sintel_nc_bf16"
CELL_LIMITS = harness.load_json(os.path.join(ROOT, "benchmark/limits", CELL + ".json"))["limits"]
CONFIG = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel-bf16.json"))
F32_CONFIG = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel.json"))
TRAIN_CONFIG = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel-ft-bf16.json"))
TOY_TRAFFIC = {"native_hw": [60, 96], "iters": 2, "batch_size": 2, "pool": 2,
               "pairs_per_pass": 2, "check_pairs": 1, "num_workers": 1}
TALLY_ROWS = ("pinned_sites_not_f32", "compute_sites_not_bf16", "f32_product_sites_gap")
HLO_ROWS = ("hlo_compute_products_not_bf16", "hlo_pinned_ops_narrow", "hlo_sums_not_f32")
# the training cell's three, and this cell's own: the pins' products at ``highest``
CELL_HLO_ROWS = HLO_ROWS + ("hlo_pinned_products_not_highest",)
SITE_ROWS = ("product_site_gap", "accumulate_bf16_site_gap_negated", "lookup_site_gap",
             "upsample_site_gap_px")
WINDOW_ROWS = ("window_px_count_gap", "window_nonfinite_sums", "flow_gap_mean_px")
EVAL_METRICS = {"compile_s", "device_ms_per_pair", "device_idle_pct.infer",
                "eval_input_wait_ms_per_pair", "eval_input_stage_ms_per_pair",
                "eval_input_h2d_ms_per_pair"}
MIXED_METRICS = {"infer_mfu_pct", "infer_f32_product_sites"}
STARTUP_SEVEN = ["setup_trace_lower_s", "setup_program_load_s", "setup_first_run_s",
                 "setup_input_start_s", "setup_cache_miss_programs", "setup_unattributed_s",
                 "eval_pass_start_p50_ms"]
TRAIN_MIXED_TWO = ["train_step_mfu_pct", "train_f32_product_sites"]
# CPU, 64x96, 3 iterations, seeded random weights. The bf16_infer forward
# against the mixed reference reads 7.4e-3 px and against the float32
# reference 7.4e-3 px too (flow of ~1 px mean magnitude): two bfloat16
# computations of one policy stand as far apart as bfloat16 stands from
# float32, because XLA keeps float32 inside fused expressions and every flipped
# rounding is fed back through the iterations. The tolerance is four times
# the reading; what it holds is that the forward IS the policy's to rounding
# (a forward with the coordinate carry in bfloat16 reads 0.16 px). The site
# rows take the cell's own limits, set on the chip.
TOY_FLOW_GAP_PX = 0.03
TOY_LIMITS = {**CELL_LIMITS, "flow_gap_mean_px": TOY_FLOW_GAP_PX}


def toy_tree(tmp_path, precision: str = "bf16_infer", drops=None) -> str:
    """A checkout-like tree whose one cell ``toy`` is ``eval_sintel_nc_bf16``
    at a toy size: configuration, traffic and limits files beside the real
    ones, found by name."""
    root = str(tmp_path / "tree")
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    base = os.path.join(root, "benchmark")
    config = json.loads(json.dumps(CONFIG))
    config["model"]["precision"] = precision
    if drops is not None:
        config["control"]["drop"] = list(drops)
    traffic = harness.load_json(os.path.join(base, "traffic", "eval_sintel_b16_mixed.json"))
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


def toy_cell(root: str, seed: int = 2**31 + 7):
    return harness.Cell(root, harness.load_json(os.path.join(root, "BENCHMARK.json")), "toy", seed)


def lines_of(capsys) -> list:
    return [json.loads(x) for x in capsys.readouterr().out.strip().splitlines() if x.startswith("{")]


# ----------------------------------------------------------- BENCHMARK.json


def test_the_cell_and_its_files_are_declared():
    cell = harness.Cell(ROOT, BENCH, CELL, 1)
    assert cell.workload["chips"] == 1 and cell.traffic["driver"] == "eval_pass_mixed"
    assert len(cell.workload["why"]) <= 200
    assert {"flow_gap_mean_px", "product_site_gap", "lookup_site_gap", "upsample_site_gap_px"} | set(
        TALLY_ROWS + CELL_HLO_ROWS) <= set(cell.limits)
    assert all(cell.limits[name] == 0 for name in TALLY_ROWS + CELL_HLO_ROWS)
    assert {m["name"] for m in harness.metrics_of(BENCH["end_to_end"], CELL)} == {"pairs_per_s", "setup_s"}
    per_layer = {m["name"] for m in harness.metrics_of(BENCH["per_layer"], CELL)}
    assert per_layer == EVAL_METRICS | MIXED_METRICS  # none of PR 35's seven
    # the float32 evaluation cell's model, letter for letter but for the precision
    assert CONFIG["widths"] == F32_CONFIG["widths"]
    assert CONFIG["model"] == {**F32_CONFIG["model"], "precision": "bf16_infer"}
    assert CONFIG["runtime"] == {"jax_default_matmul_precision": "highest"} and CONFIG["reduced"] == []
    f32_traffic = harness.load_json(os.path.join(ROOT, "benchmark/traffic/eval_sintel.json"))
    changed = {k for k in cell.traffic if cell.traffic[k] != f32_traffic.get(k)}
    assert changed == {"driver", "what", "batch_size", "pool", "pairs_per_pass"}
    # ISSUE 39's traffic as issued (memory read 8.64 GB: no batch-24 departure)
    assert (cell.traffic["batch_size"], cell.traffic["pairs_per_pass"], cell.traffic["pool"]) == (16, 64, 32)
    # P9's "at highest" lies between the program and the float32 program at one pass
    # (4.45e-6 and 2.43e-5 px on the chip, benchmark/limits/)
    assert 4.45e-6 < cell.limits["upsample_site_gap_px"] < 2.43e-5
    assert tuple(CONFIG["control"]["drop"]) == CONTROLS and "reference" not in CONFIG["control"]
    stated = CONFIG["precision"]
    assert stated["preset"] == "bf16_infer" and len(stated["points"]) == 10
    # the sites and sums are the training configuration's, name for name: one policy, two phases
    for key in ("compute_scopes", "pinned_sites", "float32_sums"):
        assert stated[key] == TRAIN_CONFIG["precision"][key]
    assert stated["pinned_scopes"] == TRAIN_CONFIG["precision"]["pinned_scopes"] + ["raft.metric_head"]
    assert all(p.split("/")[0] in stated["pinned_scopes"] for p in stated["pinned_sites"])
    assert stated["carry"] == {"net": "bfloat16", "coords1": "float32"}
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/raft_nc_dbl-sintel-bf16.json" and entry["reduced"] == []


def test_the_appended_entries_keep_the_order_of_what_was_there():
    """What ``test_startup_readers.py:66`` and ``test_train_mixed_cell.py:138``
    guarded, as ORDER and not as place: PR 35's seven in their order among
    themselves, PR 37's two after them, this PR's two after those, and every
    earlier entry before all of them. (A place pin from the list's end fails
    for every PR that appends, and the driver takes an entry put anywhere but
    at the end for a change to those behind it: PERF.md section 7.)"""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    tail = STARTUP_SEVEN + TRAIN_MIXED_TWO + ["infer_mfu_pct", "infer_f32_product_sites"]
    assert names[-len(tail):] == tail
    assert [n for n in names if n in STARTUP_SEVEN] == STARTUP_SEVEN
    mfu, sites = BENCH["per_layer"][-2:]
    assert mfu == {"name": "infer_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
                   "layer": "compiled programs", "moves": "pairs_per_s", "workloads": [CELL]}
    assert sites["source"] == "program_counter" and sites["layer"] == "model scopes"
    assert sites["moves"] == "pairs_per_s" and sites["workloads"] == [CELL]


@pytest.mark.parametrize("name", ["setup_first_run_s", "setup_input_start_s"])
def test_a_startup_entry_is_what_it_was(name):
    """The two cases of ``test_startup_readers.py::test_new_entries_resolve_to_
    files_in_their_cells`` that this PR's two appended entries push over that
    test's place pin: every other assertion of theirs."""
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    assert callable(harness.load_module(path).read)
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert m["layer"] == "entry points" and m["better"] == "lower"
    assert m["moves"] == "setup_s" and m["source"] == "program_span"
    evals = ["eval_sintel_nc", "eval_sintel_raft"]
    want = evals if name == "setup_input_start_s" else ["eval_sintel_nc", "serve_sintel_raft", "eval_sintel_raft"]
    assert m["workloads"] == want


def test_the_training_cell_is_declared_as_it_was():
    """``test_train_mixed_cell.py::test_the_cell_and_its_files_are_declared``
    without its place pin (line 138), which any appended entry fails."""
    name = "train_sintel_nc_bf16"
    cell = harness.Cell(ROOT, BENCH, name, 1)
    assert cell.workload["chips"] == 1 and cell.traffic["driver"] == "train_steps_mixed"
    assert all(cell.limits[row] == 0 for row in TALLY_ROWS + HLO_ROWS)
    assert {m["name"] for m in harness.metrics_of(BENCH["end_to_end"], name)} == {"pairs_per_s", "setup_s"}
    train = {"train_device_ms_per_step", "device_idle_pct.train", "train_input_wait_ms_per_step",
             "train_dispatch_p50_ms", "compile_s"}
    assert {m["name"] for m in harness.metrics_of(BENCH["per_layer"], name)} == train | set(TRAIN_MIXED_TWO)
    assert {m["name"] for m in harness.metrics_of(BENCH["per_layer"], "train_sintel_nc")} == train
    f32 = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel-ft.json"))
    assert TRAIN_CONFIG["train"] == f32["train"] and TRAIN_CONFIG["widths"] == f32["widths"]
    assert TRAIN_CONFIG["model"] == {**f32["model"], "precision": "bf16_train"}
    assert TRAIN_CONFIG["runtime"] == {"jax_default_matmul_precision": "highest"} and TRAIN_CONFIG["reduced"] == []
    f32_traffic = harness.load_json(os.path.join(ROOT, "benchmark/traffic/train_sintel_ft.json"))
    assert cell.traffic == {**f32_traffic, "driver": "train_steps_mixed"}
    assert "reference" not in TRAIN_CONFIG["control"]
    assert len(TRAIN_CONFIG["precision"]["points"]) == 10 and len(TRAIN_CONFIG["precision"]["pinned_sites"]) == 11
    mfu = next(m for m in BENCH["per_layer"] if m["name"] == "train_step_mfu_pct")
    assert mfu["source"] == "device_trace" and mfu["moves"] == "pairs_per_s"
    entry = next(c for c in BENCH["configs"] if c["name"] == TRAIN_CONFIG["name"])
    assert entry["source"] == TRAIN_CONFIG["source"] and len(entry["source"]) <= 200


def test_the_operation_count_has_ncup_once():
    """``infer_mfu_pct`` reads ``benchmark/flops.py`` through the window's
    info line: the test-mode program runs NCUP once, after the loop."""
    model = CONFIG["model"]
    once = flops.forward_flops(model, 1, 440, 1024, 32)
    per_iteration = flops.forward_flops(model, 1, 440, 1024, 33) - once
    every = flops.forward_flops(model, 1, 440, 1024, 32, upsample_every_iteration=True)
    ncup = (every - once) / 31
    assert ncup > 0 and abs(per_iteration - flops._update_block(55, 128, 324)) < 1.0
    assert abs(once - 1.42e12) < 0.01e12  # PERF.md section 2


# ------------------------------------------------------------ the reference


def test_the_mixed_reference_is_independent_of_the_program():
    import benchmark.reference.raft_infer_mixed as mixed

    source = open(mixed.__file__).read()
    assert "import raft_ncup_tpu" not in source and "from raft_ncup_tpu" not in source


@pytest.fixture(scope="module")
def toy_forward():
    """Seeded weights, one 64x96 pair, 3 iterations: the flow of both
    references, the mixed reference's low-resolution state, and the program's
    flow under ``bf16_infer`` through ``ShapeCachedForward``."""
    import jax

    from benchmark.program import build_model
    from raft_ncup_tpu.inference.pipeline import ShapeCachedForward

    ref = Reference(F32_CONFIG["model"])
    variables = ref.init_variables(2**31 + 11)
    pair = traffic_gen.make_pair(np.random.default_rng(659), (64, 96), 6.0)
    i1, i2 = (np.asarray(pair[k], np.float32)[None] for k in ("image1", "image2"))
    mixed = MixedInferReference(CONFIG["model"])
    with jax.default_matmul_precision("highest"):
        net, coords1 = mixed.state(variables, i1, i2, 3)
        fwd = ShapeCachedForward(build_model(CONFIG["model"]), variables)
        _, program = fwd.forward_device(i1, i2, 3)
        return {
            "variables": variables, "images": (i1, i2), "mixed": mixed, "fwd": fwd,
            "state": (net, coords1), "program": np.asarray(program),
            "mixed_flow": np.asarray(mixed.upsample(variables, net, coords1)),
            "float32_flow": np.asarray(ref.flow(variables, i1, i2, 3)),
        }


def epe(a, b) -> float:
    return float(np.sqrt(((np.asarray(a) - np.asarray(b)) ** 2).sum(-1)).mean())


def test_the_bf16_infer_forward_agrees_with_both_references(toy_forward):
    t = toy_forward
    to_mixed, to_f32 = epe(t["program"], t["mixed_flow"]), epe(t["program"], t["float32_flow"])
    assert 0.0 < to_mixed <= TOY_FLOW_GAP_PX and 0.0 < to_f32 <= TOY_FLOW_GAP_PX, (to_mixed, to_f32)
    assert t["program"].dtype == np.float32  # P10: the flow handed out
    # the executable's key carries the policy's name, and its tally is banked
    report = t["fwd"].report()
    (key,) = [k for k in t["fwd"].costs.keys() if "'bf16_infer'" in k and "(1, 64, 96, 3)" in k]
    assert report["precision"]["preset"] == "bf16_infer"
    assert (report["precision"]["sites_f32"], report["precision"]["sites_bf16"]) == (11, 52)
    f32_sites = sorted(p for p, s in report["precision"]["sites"].items() if s["operands"] == "float32")
    assert f32_sites == CONFIG["precision"]["pinned_sites"]  # name for name


def test_the_loops_carry_is_what_p8_states(toy_forward):
    """``net`` rides the loop in bfloat16, ``coords1`` in float32: read from
    the module the compiler is handed (the forward's one ``while``)."""
    text = toy_forward["fwd"].lowered_hlo()
    (loop,) = [line for line in text.splitlines() if " while(" in line]
    carried = loop.split("= (", 1)[1].split(") while(", 1)[0].split(", ")
    # the counter, then the carry; the loop's constants (the pyramid, the
    # context's float32 accumulators, the kernels) ride the tuple behind it
    assert [t.split("{")[0] for t in carried[:3]] == ["s32[]", "bf16[1,8,12,128]", "f32[1,8,12,2]"]
    assert not any(t.startswith("bf16[1,8,12,2]") for t in carried)  # no coordinate is narrow


@pytest.mark.parametrize("drop", CONTROLS)
def test_a_control_fails_the_row_that_holds_it(toy_forward, drop):
    """One statement of the policy dropped: the control moves the row its
    statement is held by, past the cell's own limit, and leaves the other site
    rows at exactly 0. ``coords_bf16`` is held by the whole forward's gap
    (read here at the toy size; the three others' forwards on the chip)."""
    from benchmark.reference.raft_train_mixed import site_inputs, site_products

    t = toy_forward
    variables, (net, coords1) = t["variables"], t["state"]
    low = MixedInferReference(CONFIG["model"], drop=drop)
    inputs = site_inputs(variables["params"], 5, (8, 12))
    want = site_products(variables["params"], inputs)
    got = site_products(variables["params"], inputs, drop=drop)
    product = min(
        float(np.linalg.norm(np.asarray(got[s] - want[s])) / np.linalg.norm(np.asarray(want[s])))
        for s in want
    )
    f1, f2, coords = lookup_site_inputs(5, (8, 12))
    sound = np.asarray(lookup_site(f1, f2, coords, 4, 4))
    lookup = float(np.linalg.norm(np.asarray(lookup_site(f1, f2, coords, 4, 4, drop)) - sound)
                   / np.linalg.norm(sound))
    upsample = epe(low.upsample(variables, net, coords1), t["mixed_flow"])
    read = {"product_site_gap": product, "lookup_site_gap": lookup, "upsample_site_gap_px": upsample}
    if drop == "coords_bf16":  # P8 is the whole flow's: a carry in bfloat16 is another answer
        i1, i2 = t["images"]
        gap = epe(low.flow(variables, i1, i2, 3), t["float32_flow"])
        assert gap > 3 * TOY_FLOW_GAP_PX, gap
        assert read["upsample_site_gap_px"] > 0.0  # NCUP reads a rounded flow
        assert read["product_site_gap"] == read["lookup_site_gap"] == 0.0
        return
    held_by = {"accumulate_bf16": "product_site_gap", "lookup_bf16": "lookup_site_gap",
               "upsampler_bf16": "upsample_site_gap_px"}[drop]
    assert read[held_by] > 2 * CELL_LIMITS[held_by], read
    assert all(v == 0.0 for k, v in read.items() if k != held_by), read


def test_a_control_name_the_reference_lacks_is_refused():
    with pytest.raises(ValueError, match="no control"):
        MixedInferReference(CONFIG["model"], drop="volume_f16")


# ------------------------------------------------- the entry point's switch


def test_mixed_precision_and_precision_bf16_infer_build_one_executable_key(monkeypatch):
    """``evaluate.py --mixed_precision`` and ``--precision bf16_infer`` resolve
    to one preset, and the metric pass's executable key is the same tuple,
    with the policy's name in it."""
    from raft_ncup_tpu.cli import parse_eval
    from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
    from raft_ncup_tpu.models.raft import RAFT

    keys = []
    monkeypatch.setattr(ShapeCachedForward, "_get", lambda self, key, build: (
        keys.append(tuple(key)) or (lambda *args: None)))
    batch = {k: np.zeros((2, 64, 96, c), np.float32) for k, c in (("image1", 3), ("image2", 3), ("flow", 2))}
    for switch in (["--mixed_precision"], ["--precision", "bf16_infer"], []):
        _, model_cfg, _ = parse_eval(["--model", "raft_nc_dbl", "--dataset", "sintel", *switch])
        fwd = ShapeCachedForward(RAFT(model_cfg), {})
        fwd.metrics(batch, iters=32, acc=None, kind="px", pad=None)
    assert keys[0] == keys[1] and keys[0][-1] == "bf16_infer"
    assert keys[2][-1] == "f32" and keys[2][:-1] == keys[0][:-1]


# --------------------------------------------- the driver, through a run


def test_toy_run_is_correct_and_reports_the_tally(tmp_path, capsys, monkeypatch):
    """One traced toy run of the cell: ``correct``, every compared row beside
    its limit, the pass's precision report, the gauges, the cell's per-layer
    metrics (the share of the chip's peak needs a chip: off the TPU the line
    leaves it out)."""
    from benchmark import trace_reduce

    monkeypatch.setattr(
        trace_reduce, "reduce_trace_dir",
        lambda d: {"busy_s": 0.9, "window_s": 1.0, "layout": {},
                   "device_ops": [["fusion.1", 0.4]], "idle_gaps": [["bench.window", 0.1]]},
    )
    res = harness.run_cell("toy", 2**31 + 7, 0.3, 1, t_start=time.perf_counter(),
                           root=toy_tree(tmp_path), require_tpu=False)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == (EVAL_METRICS | MIXED_METRICS) - {"infer_mfu_pct"}
    assert res["metrics"]["infer_f32_product_sites"] == {"value": 11, "unit": "sites"}
    lines = lines_of(capsys)
    assert lines[-1] == res
    window = next(x for x in lines if x.get("phase") == "window")
    assert set(window["end_to_end"]) == {"pairs_per_s"} and "setup_s" in next(
        x for x in lines if x.get("phase") == "setup")
    precision = window["report"]["precision"]
    assert precision["preset"] == "bf16_infer" and precision["sites_f32"] == 11
    assert precision["sites_bf16"] == len(precision["sites"]) - 11 == 52
    compared = {x["check"]: x for x in lines if "check" in x}
    assert set(WINDOW_ROWS + TALLY_ROWS + CELL_HLO_ROWS + SITE_ROWS) | {
        "compile_events_in_window", "failed"} == set(compared)
    assert all(c["ok"] for c in compared.values()), compared
    assert all(compared[name]["value"] == 0 for name in TALLY_ROWS + CELL_HLO_ROWS)
    assert compared["flow_gap_mean_px"]["value"] > 0.0  # bfloat16 did run
    # the module the compiler was handed says what the tally says
    hlo = next(x for x in lines if x.get("phase") == "hlo")["products"]
    assert hlo["raft.upsample: f32xf32->f32"] == 3 and hlo["None: bf16xbf16->f32"] == 1
    assert hlo["raft.update_block: bf16xbf16->f32"] == 7 and hlo["raft.gru_context: bf16xbf16->f32"] == 6
    assert not any(k.startswith(("raft.corr_lookup", "raft.metric_head")) for k in hlo)
    # the program's own surfaces say the same: the hub's gauges, the start-up report
    from raft_ncup_tpu.observability import get_telemetry, startup_report

    hub = get_telemetry()
    assert hub.registry.get("infer_product_sites_f32").value == 11
    assert hub.registry.get("infer_product_sites_bf16").value == 52
    program = [p for p in startup_report()["programs"] if p["kind"] == "metrics"][-1]
    assert program["precision"] == {"policy": "bf16_infer", "sites_bf16": 52, "sites_f32": 11}


def test_the_float32_program_is_not_mistaken_for_it(tmp_path, capsys):
    """``readings.py --model-precision f32``'s path: the float32 program in
    the cell's place. Its flow agrees with the float32 reference better than
    the configuration's own program does, and it is not correct by the tally,
    by the lowered module (its element types, and NCUP's three convolutions
    that no longer say ``highest``) and by the product and lookup sites. (Off
    the TPU a float32 product is exact at any precision, so NCUP's site row
    passes here; on the chip it reads 2.4e-5 px or more, over its limit.)"""
    cell = toy_cell(toy_tree(tmp_path))
    cell.config["model"]["precision"] = "f32"
    rows = {r["check"]: r for r in cell.driver.reading(cell, 0.3)}
    assert set(rows) == set(WINDOW_ROWS[2:] + TALLY_ROWS + CELL_HLO_ROWS + SITE_ROWS)
    assert rows["hlo_pinned_products_not_highest"]["value"] == 3
    assert not rows["hlo_pinned_products_not_highest"]["ok"]
    assert rows["flow_gap_mean_px"]["ok"] and rows["upsample_site_gap_px"]["ok"]
    assert rows["hlo_pinned_ops_narrow"]["ok"] and rows["pinned_sites_not_f32"]["ok"]
    for name in ("hlo_compute_products_not_bf16", "compute_sites_not_bf16", "f32_product_sites_gap"):
        assert rows[name]["value"] == 52 and not rows[name]["ok"]
    assert not rows["product_site_gap"]["ok"] and not rows["lookup_site_gap"]["ok"]


def test_control_reads_a_control_at_the_sites_and_through_a_forward(tmp_path, capsys):
    cell = toy_cell(toy_tree(tmp_path, drops=["lookup_bf16"]))
    rows = {r["check"]: r for r in cell.driver.control(cell)}
    assert set(rows) == {"lookup_bf16." + name for name in (
        "product_site_gap", "lookup_site_gap", "upsample_site_gap_px", "flow_gap_mean_px")}
    assert not rows["lookup_bf16.lookup_site_gap"]["ok"]
    assert rows["lookup_bf16.product_site_gap"]["value"] == 0.0
    assert rows["lookup_bf16.flow_gap_mean_px"]["value"] > 0.0
    bad = toy_cell(toy_tree(tmp_path / "bad", drops=["volume_f16"]))
    with pytest.raises(harness.NoResult, match="no control"):
        bad.driver.control(bad)


def test_a_program_without_the_report_is_refused_at_once(tmp_path, monkeypatch):
    """The parent of PR 39 under this PR's benchmark files: no
    ``ShapeCachedForward.report``; the cell exits before anything compiles."""
    from raft_ncup_tpu.inference.pipeline import ShapeCachedForward

    monkeypatch.delattr(ShapeCachedForward, "report")
    t0 = time.perf_counter()
    with pytest.raises(harness.NoResult, match="report"):
        harness.run_cell("toy", 1, 0.3, 0, t_start=t0, root=toy_tree(tmp_path), require_tpu=False)
    assert time.perf_counter() - t0 < 20.0


CONV = ('  %c.{n} = f32[1,16,24,64]{{3,2,1,0}} convolution(f32[1,16,24,130]{{3,2,1,0}} %a, f32[3,3,130,64]{{3,2,1,0}} %k), '
        'window={{size=3x3 pad=1_1x1_1}}, dim_labels=b01f_01io->b01f{precision}, '
        'metadata={{op_name="jit(fn)/{scope}/conv_general_dilated"}}')


@pytest.mark.parametrize("precisions, scope, want", [
    (("highest,highest",) * 3, "raft.upsample/upsampler/weights_est_net/conv0", 0),
    (("highest,highest", None, "highest,default"), "raft.upsample/upsampler/weights_est_net/conv1", 2),
    ((None, None), "raft.update_block/encoder/convc1", 1),  # nothing under a pin: itself a reading off it
])
def test_the_pins_products_are_counted_by_their_operand_precision(precisions, scope, want):
    from benchmark.drivers.eval_pass_mixed import _pinned_products_not_highest

    text = "\n".join(
        CONV.format(n=n, scope=scope, precision="" if p is None else f", operand_precision={{{p}}}")
        for n, p in enumerate(precisions)
    )
    assert _pinned_products_not_highest(text, CONFIG["precision"]["pinned_scopes"]) == want


@pytest.mark.parametrize("name", sorted(MIXED_METRICS))
def test_the_new_readers_give_nothing_where_there_is_nothing_to_read(name):
    read = harness.load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")).read
    assert read({"report": {}, "window": {}, "setup": {}}) is None
    assert read({"report": {"precision": None}, "window": {"window_s": 1.0}, "setup": {},
                 "trace": {"busy_s": 0.5, "window_s": 1.0}}) is None


def test_the_mfu_reader_reads_the_devices_busy_time():
    read = harness.load_module(os.path.join(ROOT, "benchmark/layer_metrics/infer_mfu_pct.py")).read
    run = {"window": {"analytic_flops_utilisation_pct": 12.0, "window_s": 50.0},
           "trace": {"busy_s": 40.0, "window_s": 50.0}, "report": {}, "setup": {}}
    assert read(run) == 12.0 * 50.0 / 40.0  # of the busy seconds, not of the window's
    assert read({**run, "trace": {"busy_s": 0.0, "window_s": 50.0}}) is None


def test_the_lookup_site_reads_the_levels_as_they_are_stored(monkeypatch):
    """``eval_pass_mixed._program_lookup``: the program's pyramid and its
    lookup as TWO programs, the levels handed over as the bfloat16 arrays they
    are stored in, as the forward's loop is handed them. One program of both
    let the chip's compiler read levels 1-3 before their rounding (8.1e-4 at
    the site on a v5e, PERF.md section 6, PR 39); on the CPU both forms stand
    at rounding from the reference, so what is held here is the form: the
    lookup is lowered with bfloat16 operands, and a float32 pyramid in the
    policy's place (P6 dropped) is refused by the cell's limit."""
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import eval_pass_mixed as driver
    from benchmark.program import build_model

    f1, f2, coords = lookup_site_inputs(3, (8, 128))
    coords = coords.at[0, 0, :4, 0].set(jnp.asarray([-7.25, 0.0, 126.5, 133.0]))  # past both edges
    want = lookup_site(f1, f2, coords, 4, 4)
    lowered = []
    jit = jax.jit
    monkeypatch.setattr(driver.jax, "jit", lambda f: (
        lambda *args: lowered.append(jit(f).lower(*args).as_text()) or jit(f)(*args)))
    with jax.default_matmul_precision("highest"):
        got = driver._program_lookup(build_model(CONFIG["model"]).cfg, f1, f2, coords)
        wide = driver._program_lookup(build_model(F32_CONFIG["model"]).cfg, f1, f2, coords)
    assert driver._rel_gap(got, want) < CELL_LIMITS["lookup_site_gap"]
    assert driver._rel_gap(wide, want) > 2 * CELL_LIMITS["lookup_site_gap"]
    pyramid, lookup = lowered[:2]
    assert "reduce_precision" not in pyramid  # the program's own casts, nothing of the reference's
    (signature,) = [line for line in lookup.splitlines() if "@main(" in line]
    for shape in ("1x1024x8x128", "1x1024x4x64", "1x1024x2x32", "1x1024x1x16"):
        assert f"tensor<{shape}xbf16>" in signature
