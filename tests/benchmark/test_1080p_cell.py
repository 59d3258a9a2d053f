"""CPU rehearsals of the cell ``eval_1080p_nc`` (PR 32) at a toy shape: the
configuration's own ``model`` (``corr_impl`` "pallas", kernels in interpret
mode) under a VMEM budget small enough that level 0 takes the banded tier
and the levels under it the resident one, as levels 0-1 and 2-3 do at
1080x1920 under the chip's 16 MiB. Counts, shapes and ``correct`` only:
nothing here is a speed.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

import test_benchmark as tb  # ROOT, BENCH, drive, the toy limit
from benchmark import flops_corr, harness, meters, trace_reduce, traffic_gen
from benchmark.reference.raft import Reference, reference_flow

CELL = "eval_1080p_nc"
CONFIG = harness.load_json(os.path.join(tb.ROOT, "benchmark", "configs", "raft_nc_dbl-1080p.json"))
TOY_TRAFFIC = {"native_hw": [92, 128], "iters": 3, "batch_size": 2, "pool": 4,
               "pairs_per_pass": 4, "check_pairs": 2}
LEVELS_HW = [(12, 16), (6, 8), (3, 4), (1, 2)]  # 96x128 at 1/8, pooled
V5E = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def _shrink_vmem(patch):
    """A VMEM budget between what level 0 and level 1 of the toy shape ask
    resident: level 0 goes banded (two bands), levels 1-3 stay resident."""
    from raft_ncup_tpu.ops import corr_pallas as cpk

    ask = [cpk._level_vmem_bytes(h, w, 256, 4) for h, w in LEVELS_HW[:2]]
    patch.setattr(cpk, "_VMEM_BYTES", int((ask[0] + ask[1]) / 2 / 0.9))
    return cpk


@pytest.fixture
def toy_vmem(monkeypatch):
    cpk = _shrink_vmem(monkeypatch)
    assert not cpk.fits_vmem(12, 16, 256) and cpk.fits_vmem(6, 8, 256)
    assert cpk.band_plan(12, 16, 256)[1] >= 2
    return cpk


def toy_tree(tmp_path) -> str:
    """A checkout-like tree whose one cell ``toy`` is ``eval_1080p_nc`` with
    its traffic cut to a toy size: the new configuration, the new traffic
    file's other parameters, the new readers, found by name."""
    root = str(tmp_path / "tree")
    shutil.copytree(
        os.path.join(tb.ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    cell = next(w for w in tb.BENCH["workloads"] if w["name"] == CELL)
    t = harness.load_json(os.path.join(root, "benchmark", "traffic", cell["traffic"] + ".json"))
    t.update(TOY_TRAFFIC)
    with open(os.path.join(root, "benchmark", "traffic", "toy.json"), "w") as f:
        json.dump(t, f)
    with open(os.path.join(root, "benchmark", "limits", "toy.json"), "w") as f:
        json.dump({"limits": {"flow_gap_mean_px": tb.TOY_LIMIT_PX}}, f)
    bench = json.loads(json.dumps(tb.BENCH))
    bench["workloads"] = [{**cell, "name": "toy", "traffic": "toy"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["toy"] if CELL in m["workloads"] else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# ------------------------------------------------------ the cell, rehearsed


@pytest.mark.parametrize("broken", [None, "resident", "banded"],
                         ids=["sound", "resident_levels_zeroed", "banded_level_zeroed"])
def test_toy_cell_is_correct_unless_a_level_of_the_lookup_is_zeroed(
    tmp_path, toy_vmem, monkeypatch, broken
):
    import jax.numpy as jnp

    if broken:
        name = {"resident": "_lookup_one_level", "banded": "_banded_lookup_one_level"}[broken]
        sound = getattr(toy_vmem, name)
        monkeypatch.setattr(
            toy_vmem, name, lambda *a, **k: jnp.zeros_like(sound(*a, **k))
        )
    toy_vmem.reset_dispatch_counts()
    res = tb.drive(toy_tree(tmp_path))
    tiers = toy_vmem.dispatch_counts()
    assert tiers["fallback"] == 0 and tiers["banded"] > 0 and tiers["kernel"] > 0
    assert tiers["kernel"] == 3 * tiers["banded"]  # every trace: 1 banded + 3 resident
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["correct"] is (broken is None)
    assert set(res["metrics"]) == {"pairs_per_s", "setup_s"}


# ------------------------------------------- the yardstick and its readers


def reader(name: str):
    return harness.load_module(
        os.path.join(tb.ROOT, "benchmark", "layer_metrics", name + ".py")
    )


@pytest.mark.parametrize("h8,w8,ops,moved", [
    # 1088x1920 / 8: 32,640 queries x 100 patch taps x 256 channels x 4
    # levels x 2; fmap1 8,355,840 + pyramid (32,640 + 8,160 + 2,040 + 510)
    # x 256 = 11,097,600 + coordinates 65,280 + output 32,640 x 324 =
    # 10,575,360 floats, 4 bytes each.
    (136, 240, 6_684_672_000, 4 * (8_355_840 + 11_097_600 + 65_280 + 10_575_360)),
    # 1080x1920 / 8, what the cell runs (1080 is a multiple of 8: unpadded):
    # 32,400 queries; levels 135x240, 67x120, 33x60, 16x30 = 42,900 cells.
    (135, 240, 6_635_520_000, 4 * (8_294_400 + 10_982_400 + 64_800 + 10_497_600)),
    # 96x128 / 8: 192 queries; levels 12x16, 6x8, 3x4, 1x2 = 254 cells.
    (12, 16, 2 * 192 * 100 * 256 * 4, 4 * (192 * 256 + 254 * 256 + 192 * 2 + 192 * 324)),
], ids=["1088x1920", "1080x1920", "96x128"])
def test_lookup_work_against_a_count_written_out_by_hand(h8, w8, ops, moved):
    model = CONFIG["model"]
    assert flops_corr.lookup_ops(model, h8, w8) == ops
    assert flops_corr.lookup_bytes(model, h8, w8) == moved
    least = flops_corr.lookup_roofline_s(model, h8, w8, 32, V5E)
    assert least["bound"] == "memory"  # 21x more time in bytes than in operations
    assert least["seconds"] == pytest.approx(32 * moved / 819e9)
    two = {**model, "corr_levels": 2}
    assert flops_corr.lookup_ops(two, h8, w8) == ops / 2


def _run(device_ops, pairs=8, key="tpu|('nomesh', 'metrics', (4, 1080, 1920, 3), (4, 1080, 1920, 2), ('flow',), 32, 'px', ((0, 0), (0, 0)), False, 'f32')"):
    return {
        "window": {"pairs": pairs, "executable_memory": [{"key": key, "temp_size_in_bytes": 1}]},
        "setup": {}, "report": {},
        "trace": {"busy_s": 9.0, "window_s": 9.5, "device_ops": device_ops, "idle_gaps": []},
    }


KERNELS_LISTED = [
    ["%while.13 while s32[],f32[4,135,240,128]", 9.0],
    ["%corr_banded_l0.13 custom-call s32[4,260,5],s32[4,32512,2],f32[4,32512,256]", 2.0],
    ["%corr_banded_l1.13 custom-call s32[4,255,5],s32[4,32512,2]", 1.5],
    ["%corr_resident_l2.13 custom-call s32[4,32512,2],f32[4,32512,256]", 1.25],
    ["%fusion.7 fusion kLoop f32[4,32512,9,9]", 1.0],
    ["%corr_resident_l3.13 custom-call s32[4,32512,2]", 1.25],
]
PARENT_NAMES = [["%corr_lookup_banded.26 custom-call s32[4,260,5]", 3.0],
                ["%corr_lookup_resident.27 custom-call s32[4,32512,2]", 3.0]]
ROOFLINE_8_PAIRS_S = 8 * 32 * 119_356_800 / 819e9  # 0.037308 s


@pytest.mark.parametrize("name,run,want", [
    ("corr_kernel_ms_per_pair", _run(KERNELS_LISTED), 750.0),
    ("corr_kernel_ms_per_pair", _run(PARENT_NAMES), 750.0),
    ("corr_kernel_ms_per_pair", _run([["%while.3 while", 9.0], ["%fusion.7 fusion", 1.0]]), None),
    ("corr_kernel_ms_per_pair", _run(KERNELS_LISTED, pairs=0), None),
    ("corr_kernel_ms_per_pair", {"window": {"pairs": 8}, "setup": {}, "report": {}}, None),
    ("corr_kernel_roofline_pct", _run(KERNELS_LISTED), 100.0 * ROOFLINE_8_PAIRS_S / 6.0),
    ("corr_kernel_roofline_pct", _run([["%fusion.7 fusion", 1.0]]), None),
    ("corr_kernel_roofline_pct", _run(KERNELS_LISTED, pairs=0), None),
    ("corr_kernel_roofline_pct", _run(KERNELS_LISTED, key="('forward', 1)"), None),
    ("corr_kernel_roofline_pct", {"window": {"pairs": 8}, "setup": {}, "report": {}}, None),
], ids=["ms-listed", "ms-parent-names", "ms-not-listed", "ms-no-pairs", "ms-untraced",
        "pct-listed", "pct-not-listed", "pct-no-pairs", "pct-no-key", "pct-untraced"])
def test_kernel_readers_on_a_hand_made_run(monkeypatch, name, run, want):
    """The chip's peaks stand in for the CPU's missing ones: the arithmetic is
    what is tested, and 0.6218% is what 6 s of kernels for 8 pairs reads."""
    monkeypatch.setattr(meters, "load_peaks", lambda kind: V5E)
    got = reader(name).read(run)
    assert got == (pytest.approx(want) if want is not None else None)
    assert want is None or 0.0 < got <= (750.0 if "ms" in name else 100.0)


def test_roofline_reader_reads_nothing_on_a_device_without_peaks():
    assert reader("corr_kernel_roofline_pct").read(_run(KERNELS_LISTED)) is None  # a CPU


def test_traced_toy_cell_reports_the_kernel_metrics(tmp_path, toy_vmem, monkeypatch):
    """The result line of a traced run carries both new metrics beside the
    six accepted ones the cell is listed for; the reduction is stubbed, a
    CPU trace has no device plane."""
    monkeypatch.setattr(meters, "load_peaks", lambda kind: V5E)
    monkeypatch.setattr(
        trace_reduce, "reduce_trace_dir",
        lambda d: {"busy_s": 0.5, "window_s": 1.0, "layout": {}, "idle_gaps": [],
                   "device_ops": [["%while.1 while", 0.5], ["%corr_banded_l0.3 custom-call", 0.1],
                                  ["%corr_resident_l1.4 custom-call", 0.1]]},
    )
    res = tb.drive(toy_tree(tmp_path), trace=1)
    got = res["metrics"]
    assert res["correct"] is True
    assert set(got) == {
        "compile_s", "device_ms_per_pair", "device_idle_pct.infer",
        "eval_input_wait_ms_per_pair", "eval_input_stage_ms_per_pair",
        "eval_input_h2d_ms_per_pair", "corr_kernel_ms_per_pair", "corr_kernel_roofline_pct",
    }
    pairs = res["attempted"]
    assert got["corr_kernel_ms_per_pair"]["value"] == pytest.approx(200.0 / pairs)
    least = flops_corr.lookup_roofline_s(CONFIG["model"], 12, 16, 3, V5E)["seconds"]
    assert got["corr_kernel_roofline_pct"]["value"] == pytest.approx(100 * least * pairs / 0.2)
    assert [op[0] for op in res["breakdown"]["device_ops"]][1:] == [
        "%corr_banded_l0.3 custom-call", "%corr_resident_l1.4 custom-call"]


# ------------------------------------- the program on the pallas path, CPU


def test_configuration_is_the_sintel_model_on_the_volume_free_path():
    sintel = harness.load_json(
        os.path.join(tb.ROOT, "benchmark", "configs", "raft_nc_dbl-sintel.json"))
    assert CONFIG["model"] == {**sintel["model"], "corr_impl": "pallas"}
    assert CONFIG["runtime"] == sintel["runtime"] and CONFIG["control"] == sintel["control"]
    assert CONFIG["reduced"] == [] and CONFIG["widths"] == sintel["widths"]
    cell = harness.Cell(tb.ROOT, tb.BENCH, CELL, 1)
    t = cell.traffic
    assert (t["driver"], t["native_hw"], t["pad_mode"], t["iters"], t["batch_size"]) == (
        "eval_pass", [1080, 1920], "sintel", 32, 4)
    assert (t["pool"], t["pairs_per_pass"], t["max_flow_px"], t["check_pairs"]) == (8, 8, 24, 4)
    assert cell.workload["chips"] == 1 and 0 < cell.limit("flow_gap_mean_px") < 1e-3


@pytest.mark.parametrize("precision,close", [("f32", True), ("bf16_infer", False)])
def test_pallas_program_agrees_with_the_plain_reference(toy_vmem, precision, close):
    """Seeded weights, 92x128 padded to 96x128, 4 iterations: the program
    with the lookup in the kernels (level 0 banded in two bands, levels 1-3
    resident) against ``benchmark/reference/raft.py``, which holds the
    volume, to float32 rounding; the program's bf16 preset is seen."""
    import jax
    import jax.numpy as jnp

    from benchmark.program import build_model

    model = CONFIG["model"]
    ref = Reference(model)
    variables = ref.init_variables(2**31 + 11)
    pair = traffic_gen.make_pair(np.random.default_rng(5), (92, 128), 6.0)
    flow = reference_flow(ref, variables, pair["image1"], pair["image2"], 4)
    i1, i2 = (
        jnp.asarray(np.pad(pair[k], ((2, 2), (0, 0), (0, 0)), mode="edge"), jnp.float32)[None]
        for k in ("image1", "image2")
    )
    toy_vmem.reset_dispatch_counts()
    with jax.default_matmul_precision("highest"):
        _, up = build_model({**model, "precision": precision}).apply(
            variables, i1, i2, iters=4, test_mode=True)
    tiers = toy_vmem.dispatch_counts()
    assert tiers["fallback"] == 0 and tiers["kernel"] >= 1
    assert tiers["banded"] >= 1 or not close  # at two bytes a feature every toy level fits
    gap = float(np.sqrt(((np.asarray(up)[0, 2:-2] - flow) ** 2).sum(-1)).mean())
    assert (gap < 1e-4) if close else (gap > tb.TOY_LIMIT_PX)


@pytest.fixture(scope="module")
def lowered_text():
    """The toy forward on the pallas path, lowered for a TPU target with the
    kernels in (not interpret mode), debug info kept: the Pallas calls'
    names and the scopes of the work around them are in the text."""
    import jax
    import jax.numpy as jnp

    from benchmark.program import build_model
    from raft_ncup_tpu.utils import runtime

    mp = pytest.MonkeyPatch()
    _shrink_vmem(mp)
    mp.setattr(runtime, "is_tpu_backend", lambda: True)
    try:
        model = build_model(CONFIG["model"])
        variables = jax.eval_shape(lambda k: model.init(k, (1, 96, 128, 3)), jax.random.key(0))
        img = jax.ShapeDtypeStruct((2, 96, 128, 3), jnp.float32)
        lowered = jax.jit(
            lambda v, a, b: model.apply(v, a, b, iters=3, test_mode=True)
        ).trace(variables, img, img).lower(lowering_platforms=("tpu",))
        return lowered.as_text(debug_info=True)
    finally:
        mp.undo()


@pytest.mark.parametrize("name", [
    "corr_banded_l0", "corr_resident_l1", "corr_resident_l2", "corr_resident_l3",
    "raft.corr_lookup.band_sort", "raft.corr_lookup.pad_levels", "raft.corr_lookup/",
])
def test_kernel_names_and_scopes_are_in_the_lowered_text(lowered_text, name):
    assert name in lowered_text


def test_padded_pyramid_is_made_before_the_loop_not_in_it(lowered_text):
    """Every ``pad`` of the lookup carries the ``pad_levels`` scope and none
    lies under the refinement loop's ``while``: the pooled, padded levels are
    made once per pair."""
    pads = [line for line in lowered_text.splitlines() if "raft.corr_lookup.pad_levels" in line]
    assert pads and not any("/while/" in line for line in pads)
    assert not any("corr_lookup.pad_levels" not in line and "stablehlo.pad" in line
                   and "raft.corr_lookup" in line for line in lowered_text.splitlines())
