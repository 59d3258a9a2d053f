"""The KITTI evaluation cell's own tests, on the CPU at a toy size: the
KITTI-configured program (weights net without BatchNorm, bottom padding, a
sparse mask, fill rows) against the plain reference
``benchmark/reference/raft_kitti.py`` in each of two native sizes, the
``bf16_infer`` preset failing the same comparison, upstream's numbers in
plain numpy against the pass's accumulator, and the driver
``eval_pass_kitti`` through ``harness.run_cell(..., require_tpu=False)``:
sound, with the vertical pad moved, with the mask dropped, on a program that
does not group by size. The order of ``per_layer`` is held as ORDER among the
earlier entries, never as place from the list's end. Nothing here is a speed.
"""

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

from benchmark import harness  # noqa: E402
from benchmark.drivers import eval_pass_kitti as driver  # noqa: E402
from benchmark.reference import raft_kitti  # noqa: E402
from benchmark.reference.raft import Reference  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "eval_kitti_nc"
CONFIG = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-kitti.json"))
SINTEL_CONFIG = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel.json"))
LIMITS = harness.load_json(os.path.join(ROOT, "benchmark/limits", CELL + ".json"))
# Two native sizes that pad to 64x96 from different sides: 61x93 takes 3 rows
# below and 1 + 2 columns, 58x96 six rows below and no column. 3 + 2 pairs a
# pass at batch 2: one remainder, one fill row of 6 rows.
TOY_TRAFFIC = {
    "sizes": [{"native_hw": [61, 93], "pairs": 3}, {"native_hw": [58, 96], "pairs": 2}],
    "iters": 2, "batch_size": 2, "pool_per_size": 2, "check_pairs_per_size": 1,
    "num_workers": 1,
}
# CPU, 64x96, 2 iterations, seeded weights, the worse of the two sizes on
# three seeds: the program reads 8.3e-7 to 1.1e-6 px against the reference,
# bf16_infer 5.0e-3 to 6.0e-3, a vertical pad at the other edge 0.22 to 0.27.
# The cell's own limit is set on the chip; this one is the toy's.
TOY_FLOW_GAP_PX = 1e-4
EXACT_ROWS = ("window_frames_gap", "window_valid_px_gap", "window_nonfinite_sums",
              "window_program_builds", "window_fill_rows_gap")
EVAL_SIX = ["compile_s", "device_ms_per_pair", "device_idle_pct.infer",
            "eval_input_wait_ms_per_pair", "eval_input_stage_ms_per_pair",
            "eval_input_h2d_ms_per_pair"]
KITTI_TWO = ["eval_fill_rows_pct", "eval_program_builds_per_pass"]
STARTUP_SEVEN = ["setup_trace_lower_s", "setup_program_load_s", "setup_first_run_s",
                 "setup_input_start_s", "setup_cache_miss_programs", "setup_unattributed_s",
                 "eval_pass_start_p50_ms"]
TRAIN_MIXED_TWO = ["train_step_mfu_pct", "train_f32_product_sites"]
INFER_MIXED_TWO = ["infer_mfu_pct", "infer_f32_product_sites"]
# the entries PR 23 to PR 32 brought, in their order: all of them before PR 35's seven
BEFORE_THE_SEVEN = [
    "compile_s", "serve_queue_wait_p50_ms", "serve_drain_p50_ms", "device_ms_per_pair",
    "device_idle_pct.infer", "serve_pad_stage_p50_ms", "serve_dispatch_p50_ms",
    "serve_throttle_wait_p50_ms", "serve_device_wait_p50_ms", "serve_pull_p50_ms",
    "eval_input_wait_ms_per_pair", "eval_input_stage_ms_per_pair", "eval_input_h2d_ms_per_pair",
    "train_device_ms_per_step", "device_idle_pct.train", "train_input_wait_ms_per_step",
    "train_dispatch_p50_ms", "stream_queue_wait_p50_ms", "stream_pad_stage_p50_ms",
    "stream_dispatch_p50_ms", "stream_throttle_wait_p50_ms", "stream_device_wait_p50_ms",
    "stream_pull_p50_ms", "stream_cold_start_pct", "stream_padded_rows_pct",
    "corr_kernel_ms_per_pair", "corr_kernel_roofline_pct",
]


def toy_tree(tmp_path, precision: str = "f32") -> str:
    """A checkout-like tree whose one cell ``toy`` is ``eval_kitti_nc`` at a
    toy size: configuration, traffic and limits files beside the real ones."""
    root = str(tmp_path / "tree")
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    base = os.path.join(root, "benchmark")
    config = json.loads(json.dumps(CONFIG))
    config["model"]["precision"] = precision
    traffic = harness.load_json(os.path.join(base, "traffic", "eval_kitti.json"))
    traffic.update(TOY_TRAFFIC)
    limits = {"limits": {"flow_gap_mean_px": TOY_FLOW_GAP_PX}}
    for sub, body in (("configs", config), ("traffic", traffic), ("limits", limits)):
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


def toy_cell(root: str, seed: int = 2**31 + 11):
    return harness.Cell(root, harness.load_json(os.path.join(root, "BENCHMARK.json")), "toy", seed)


def lines_of(capsys) -> list:
    return [json.loads(x) for x in capsys.readouterr().out.strip().splitlines() if x.startswith("{")]


def rows_of(lines: list) -> dict:
    return {x["check"]: x for x in lines if "check" in x}


# ----------------------------------------------------------- BENCHMARK.json


def test_the_cell_and_its_files_are_declared():
    cell = harness.Cell(ROOT, BENCH, CELL, 1)
    assert cell.workload["chips"] == 1 and cell.traffic["driver"] == "eval_pass_kitti"
    assert len(cell.workload["why"]) <= 200
    assert {m["name"] for m in harness.metrics_of(BENCH["end_to_end"], CELL)} == {"pairs_per_s", "setup_s"}
    per_layer = [m["name"] for m in harness.metrics_of(BENCH["per_layer"], CELL)]
    assert per_layer == EVAL_SIX + KITTI_TWO  # none of PR 35's seven, no mfu
    # the Sintel configuration letter for letter but for the data set the model is built for
    assert CONFIG["widths"] == SINTEL_CONFIG["widths"] and CONFIG["runtime"] == SINTEL_CONFIG["runtime"]
    assert CONFIG["model"] == {**SINTEL_CONFIG["model"], "dataset": "kitti"}
    assert CONFIG["control"] == SINTEL_CONFIG["control"] and CONFIG["reduced"] == []
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/raft_nc_dbl-kitti.json"
    assert {"weights", "frames", "size_counts", "order", "valid_mask", "batch", "control"} <= set(CONFIG["assumed"])


def test_the_traffic_is_the_issues():
    t = harness.load_json(os.path.join(ROOT, "benchmark/traffic/eval_kitti.json"))
    sizes = {tuple(s["native_hw"]): s["pairs"] for s in t["sizes"]}
    assert sizes == {(375, 1242): 131, (370, 1224): 33, (374, 1238): 21, (376, 1241): 15}
    assert sum(sizes.values()) == 200
    assert (t["iters"], t["batch_size"], t["pad_mode"], t["metric_kind"]) == (24, 8, "kitti", "kitti")
    assert [(-n) % 8 for n in sizes.values()] == [5, 7, 3, 1]  # remainders 3, 1, 5, 7
    assert driver.fill_rows_a_pass(t) == 16  # of 216 rows: 7.4%
    assert (t["pool_per_size"], t["max_flow_px"], t["valid_density"]) == (12, 24.0, 0.2)
    assert (t["num_workers"], t["depth"], t["trace_seconds"]) == (2, 2, 6)
    # three padded shapes, four programs: the native shape is in the executable's key
    padded = {(-(-h // 8) * 8, -(-w // 8) * 8) for h, w in sizes}
    assert padded == {(376, 1248), (376, 1224), (376, 1240)}
    # a pass's valid pixels stay countable in float32
    assert 200 * 376 * 1242 * (2 / 3) * t["valid_density"] * 1.05 < 2**24


def test_the_limit_lies_between_its_readings():
    limit, r = LIMITS["limits"]["flow_gap_mean_px"], LIMITS["readings"]
    assert set(LIMITS["limits"]) == {"flow_gap_mean_px"}
    assert r["program_largest"] < limit < r["control_high_smallest"]
    assert r["program_seeds_read"] >= 12 and r["control_high_seeds_read"] >= 3
    assert limit < r["bf16_infer_smallest"]


# ------------------------------------------- the order of per_layer, as order


def test_the_entries_keep_their_order_among_themselves():
    """What ``test_startup_readers.py:66``, ``test_train_mixed_cell.py:138``
    and ``test_eval_mixed_cell.py::test_the_appended_entries_keep_the_order_
    of_what_was_there`` guarded, as ORDER among the entries each PR brought
    and never as place from the list's end (a later PR's appended entries are
    in none of these lists and move nothing here): the entries of PR 23 to 32
    in their order, PR 35's seven after them all, PR 37's two, PR 39's two and
    this PR's two after those, each group in its own order."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    known = BEFORE_THE_SEVEN + STARTUP_SEVEN + TRAIN_MIXED_TWO + INFER_MIXED_TWO + KITTI_TWO
    assert [n for n in names if n in known] == known
    # nothing was put between the entries that were there when this PR appended its two
    assert names[: len(known)] == known


@pytest.mark.parametrize("name", ["setup_cache_miss_programs", "setup_unattributed_s"])
def test_a_startup_entry_is_what_it_was(name):
    """The two cases of ``test_startup_readers.py::test_new_entries_resolve_
    to_files_in_their_cells`` that this PR's two appended entries push over
    that test's place pin: every other assertion of theirs."""
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    assert callable(harness.load_module(path).read)
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert m["layer"] == "entry points" and m["better"] == "lower" and m["moves"] == "setup_s"
    assert m["source"] == ("program_counter" if name == "setup_cache_miss_programs" else "program_span")
    assert m["workloads"] == ["eval_sintel_nc", "serve_sintel_raft", "eval_sintel_raft"]


def test_the_mixed_eval_entries_are_what_they_were():
    """The other assertions of ``test_eval_mixed_cell.py::test_the_appended_
    entries_keep_the_order_of_what_was_there``, which took PR 39's two for
    the list's last two."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name["infer_mfu_pct"] == {
        "name": "infer_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "compiled programs", "moves": "pairs_per_s", "workloads": ["eval_sintel_nc_bf16"]}
    sites = by_name["infer_f32_product_sites"]
    assert sites["source"] == "program_counter" and sites["layer"] == "model scopes"
    assert sites["moves"] == "pairs_per_s" and sites["workloads"] == ["eval_sintel_nc_bf16"]


@pytest.mark.parametrize("name,layer", [
    ("eval_fill_rows_pct", "host stages (data)"), ("eval_program_builds_per_pass", "entry points"),
])
def test_the_new_entries(name, layer):
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert m == {"name": name, "unit": m["unit"], "better": "lower", "source": "program_counter",
                 "layer": layer, "moves": "pairs_per_s", "workloads": [CELL]}
    assert layer in {x["layer"] for x in BENCH["per_layer"] if x["name"] not in KITTI_TWO}


# ------------------------------------------------------------- the readers


def reader(name: str):
    return harness.load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")).read


@pytest.mark.parametrize("name", KITTI_TWO)
def test_a_reader_finds_nothing_without_the_report(name):
    """The parent's program has no such counters and an older driver hands no
    report: the metric is left out and nothing raises."""
    assert reader(name)({"report": {}, "window": {}, "setup": {}}) is None
    assert reader(name)({"report": {"counters": {"eval_pairs_total": 200}, "passes": 0},
                         "window": {}, "setup": {}}) is None


def test_the_readers_read_the_windows_deltas():
    run = {"report": {"counters": {"eval_pairs_total": 600, "eval_rows_total": 648,
                                   "eval_fill_rows_total": 48},
                      "executables": {"compiles": 0, "evictions": 0}, "passes": 3},
           "window": {}, "setup": {}}
    assert reader("eval_fill_rows_pct")(run) == pytest.approx(100 * 16 / 216)
    assert reader("eval_program_builds_per_pass")(run) == 0
    run["report"]["executables"] = {"compiles": 9, "evictions": 6}
    assert reader("eval_program_builds_per_pass")(run) == 5


# ------------------------------------------------------------- the reference


def test_the_reference_leaves_the_batchnorm_out_and_pads_below():
    ref = raft_kitti.KittiReference(CONFIG["model"])
    sintel = Reference(SINTEL_CONFIG["model"])
    v, vs = ref.init_variables(5), sintel.init_variables(5)
    west, wests = (x["params"]["upsampler"]["weights_est_net"] for x in (v, vs))
    assert sorted(west) == ["conv0", "conv1", "out"]
    assert sorted(wests) == ["bn0", "bn1", "conv0", "conv1", "out"]
    assert "upsampler" not in v.get("batch_stats", {}) and "upsampler" in vs["batch_stats"]
    # every other leaf is the Sintel tree's, draw for draw
    np.testing.assert_array_equal(west["conv0"]["kernel"], wests["conv0"]["kernel"])
    np.testing.assert_array_equal(v["params"]["fnet"]["conv1"]["kernel"], vs["params"]["fnet"]["conv1"]["kernel"])
    with pytest.raises(ValueError):
        raft_kitti.KittiReference(SINTEL_CONFIG["model"])
    img = np.arange(5 * 13 * 3, dtype=np.float32).reshape(5, 13, 3)
    out, (top, left) = raft_kitti.pad_kitti(img)
    assert out.shape == (8, 16, 3) and (top, left) == (0, 1)
    np.testing.assert_array_equal(out[:5, 1:14], img)
    np.testing.assert_array_equal(out[5:, 1:14], np.broadcast_to(img[4], (3, 13, 3)))  # edge, below
    # the program's padder puts it where upstream's F.pad order puts it
    from raft_ncup_tpu.ops import InputPadder

    assert InputPadder((1, 5, 13, 3), mode="kitti").pad_spec == ((0, 3), (1, 2))


def test_upstreams_numbers_in_numpy_equal_the_accumulators():
    """``kitti_numbers`` (upstream's per-frame loop) against
    ``metrics.accumulate('kitti')`` + ``finalize`` on the same fields, fill
    rows in the batch."""
    import jax.numpy as jnp

    from raft_ncup_tpu.inference import metrics

    g = np.random.default_rng(3)
    flows = [g.normal(size=(12, 20, 2)).astype(np.float32) * 4 for _ in range(3)]
    gts = [g.normal(size=(12, 20, 2)).astype(np.float32) * 4 for _ in range(3)]
    valids = [(g.random((12, 20)) < 0.3).astype(np.float32) for _ in range(3)]
    want = raft_kitti.kitti_numbers(flows, gts, valids)
    stack = lambda xs: jnp.asarray(np.stack(xs + [xs[-1]]))  # noqa: E731  (a fill row)
    acc = metrics.accumulate(
        "kitti", metrics.init_acc("kitti"), stack(flows), stack(gts),
        valid=jnp.asarray(np.stack(valids + [np.zeros_like(valids[-1])])),
    )
    got = metrics.finalize("kitti", np.asarray(acc))
    assert np.asarray(acc)[1] == 3
    assert got["epe"] == pytest.approx(want["epe"], rel=1e-5)
    assert got["f1"] == pytest.approx(want["f1"], rel=1e-6)


# ---------------------------------------- the program against the reference


@pytest.fixture(scope="module")
def shared(tmp_path_factory):
    """One toy tree and the states built on it, so that the cases below share
    the executables of one ``ShapeCachedForward`` a precision (the mask is
    data and a moved pad is a new key: neither needs the sound programs built
    again)."""
    import jax

    root = toy_tree(tmp_path_factory.mktemp("kitti"))
    states: dict = {}
    build = driver._build

    def cached_build(cell):
        key = (cell.config["model"]["precision"], cell.seed)
        if key not in states:
            states[key] = build(cell)
        return states[key]

    # ``harness.Cell`` loads a driver by file, a new module every time: hand
    # it this file's, whose ``_build`` shares the states.
    load = harness.load_module
    with pytest.MonkeyPatch.context() as patch, jax.default_matmul_precision("highest"):
        patch.setattr(harness, "load_module", lambda path: (
            driver if path.endswith(os.path.join("drivers", "eval_pass_kitti.py")) else load(path)))
        patch.setattr(driver, "_build", cached_build)
        yield root


@pytest.mark.parametrize("precision,passes", [("f32", True), ("bf16_infer", False)])
def test_the_program_against_the_reference_in_each_size(shared, capsys, precision, passes):
    """``readings.py``'s call: a batch a size through the pass with the
    reference's flow as ground truth under the sparse mask, a bottom pad and
    a fill row in play. The float32 program stands at rounding from the
    reference in BOTH sizes; ``bf16_infer`` in its place is refused."""
    cell = toy_cell(shared)
    cell.config["model"]["precision"] = precision
    (row,) = driver.reading(cell, 1.0)
    (ref_line,) = [x for x in lines_of(capsys) if x.get("phase") == "reference"]
    by_size = ref_line["flow_gap_mean_px_by_size"]
    assert set(by_size) == {"61x93", "58x96"} and row["value"] == max(by_size.values())
    assert row["ok"] is passes
    if passes:
        assert max(by_size.values()) < TOY_FLOW_GAP_PX / 5
    else:
        assert min(by_size.values()) > 5 * TOY_FLOW_GAP_PX  # in each size, not in the worst alone


# ----------------------------------------------------- the CPU rehearsals


def run_toy(root, capsys, trace: int = 0):
    result = harness.run_cell("toy", 2**31 + 11, 0.01, trace, t_start=time.perf_counter(),
                              root=root, require_tpu=False)
    return result, lines_of(capsys)


def test_a_sound_run_is_correct_and_counts_its_rows(shared, capsys):
    result, lines = run_toy(shared, capsys)
    rows = rows_of(lines)
    assert result["correct"] is True, rows
    assert set(EXACT_ROWS) | {"flow_gap_mean_px", "compile_events_in_window", "failed"} == set(rows)
    assert all(rows[name]["value"] == 0 and rows[name]["limit"] == 0 for name in EXACT_ROWS)
    assert set(result["metrics"]) == {"pairs_per_s", "setup_s"}
    (window,) = [x for x in lines if x.get("phase") == "window"]
    assert window["passes"] == 1 and window["pairs"] == result["attempted"] == 5
    assert (window["rows"], window["fill_rows"]) == (6, 1)  # a fill row is no pair
    assert window["programs_resident"] == 2  # one a native size
    assert window["report"]["executables"] == {"compiles": 0, "evictions": 0}
    assert reader("eval_fill_rows_pct")({"report": window["report"]}) == pytest.approx(100 / 6)
    assert reader("eval_program_builds_per_pass")({"report": window["report"]}) == 0


def test_a_vertical_pad_at_the_other_edge_is_not_correct(shared, capsys, monkeypatch):
    """The program's padder with KITTI's vertical pad moved above the frame
    (and its in-graph crop with it, so every count still agrees): the flow is
    another padding's, and only the comparison with the reference says so."""
    from raft_ncup_tpu import evaluation
    from raft_ncup_tpu.ops import InputPadder

    class Moved(InputPadder):
        def __init__(self, dims, mode="sintel", **kw):
            super().__init__(dims, mode=mode, **kw)
            if mode == "kitti":
                (t, b), lr = self._pad
                self._pad = ((t + b, 0), lr)

    monkeypatch.setattr(evaluation, "InputPadder", Moved)
    result, lines = run_toy(shared, capsys)
    rows = rows_of(lines)
    assert result["correct"] is False
    assert [n for n, r in rows.items() if not r["ok"]] == ["flow_gap_mean_px"]
    assert rows["flow_gap_mean_px"]["value"] > 10 * TOY_FLOW_GAP_PX


def test_a_dropped_mask_is_not_correct(shared, capsys, monkeypatch):
    """Every pixel valid, the fill rows' too: the frames and the valid pixels
    are miscounted (a fill row counts as a frame), whatever the flow reads."""
    from raft_ncup_tpu import evaluation

    stage = evaluation._stage_batch

    def unmasked(group, **kw):
        arrays, pad = stage(group, **kw)
        arrays["valid"] = np.ones_like(arrays["valid"])
        return arrays, pad

    monkeypatch.setattr(evaluation, "_stage_batch", unmasked)
    result, lines = run_toy(shared, capsys)
    rows = rows_of(lines)
    assert result["correct"] is False
    assert rows["window_frames_gap"]["value"] == 1  # the pass's one fill row
    assert rows["window_valid_px_gap"]["value"] > 0
    assert rows["window_program_builds"]["ok"] and rows["window_fill_rows_gap"]["ok"]


def test_a_program_that_cuts_its_groups_by_run_is_refused_at_once(monkeypatch):
    """The parent of PR 45 under this PR's benchmark files: no ``fill_valid``
    on ``uniform_batches``. The driver refuses it before anything is built
    (``run.py`` then exits 2 and prints no result)."""
    from raft_ncup_tpu.inference import pipeline

    def by_run(samples, batch_size):
        raise AssertionError("never reached")

    monkeypatch.setattr(pipeline, "uniform_batches", by_run)
    with pytest.raises(harness.NoResult, match="every change of size"):
        driver._refuse_without_size_groups()


# ------------------------- the accepted eval cells' metric programs, unchanged


@pytest.mark.parametrize("kind,pad_mode", [("px", "sintel"), ("epe", None)])
def test_a_pass_without_a_mask_hands_the_program_what_it_always_did(kind, pad_mode):
    """The kinds the accepted eval cells run (``px``) have no ``valid``
    operand, get no fill row and keep their short last batch, so the module
    the compiler is handed for a batch of theirs is, letter for letter, the
    one ``ShapeCachedForward.metrics`` lowers for that batch staged by hand
    with no pipeline between: the grouping adds nothing to a program. (The
    four cells' modules at their own sizes were compared at parent and change
    by a script, PERF.md section 6, PR 45.)"""
    import jax

    from raft_ncup_tpu.config import small_model_config
    from raft_ncup_tpu.evaluation import _run_metric_pass, _stage_batch
    from raft_ncup_tpu.inference import metrics as metrics_mod
    from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
    from raft_ncup_tpu.models.raft import RAFT

    g = np.random.default_rng(2)
    hw = (36, 44) if pad_mode else (40, 48)
    samples = [{"image1": g.integers(0, 255, (*hw, 3), dtype=np.uint8),
                "image2": g.integers(0, 255, (*hw, 3), dtype=np.uint8),
                "flow": g.normal(size=(*hw, 2)).astype(np.float32)} for _ in range(3)]
    model = RAFT(small_model_config("raft", dataset="chairs"))
    variables = model.init(jax.random.PRNGKey(0), (1, 40, 48, 3))

    class Listed:
        def __len__(self):
            return len(samples)

        def sample(self, i):
            return samples[i]

    through_pass = ShapeCachedForward(model, variables)
    _run_metric_pass(through_pass, Listed(), kind=kind, iters=1, batch_size=2,
                     pad_mode=pad_mode, num_workers=1)
    assert through_pass.stats["compiles"] == 2  # the full batch and the short last one
    by_hand = ShapeCachedForward(model, variables)
    arrays, pad = _stage_batch(samples[2:], pad_mode=pad_mode)
    by_hand.metrics(arrays, iters=1, acc=metrics_mod.init_acc(kind), kind=kind, pad=pad)
    assert list(by_hand._fns) == list(through_pass._fns)[1:]  # the short batch's key, unchanged
    assert all("valid" not in key[4] for key in through_pass._fns)


    def module(fwd) -> str:  # StableHLO of the newest executable's function, no source locations
        fn = fwd._newest()[0]
        return fn._jitfn.lower(*fn._compiled_box["avals"]).as_text()

    assert module(by_hand) == module(through_pass)
