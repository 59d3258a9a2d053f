"""The warm-start deployment's tests, all on the CPU at toy sizes: the plain
reference (``benchmark/reference/raft_stream.py``) against the program's
``StreamEngine`` over whole sessions, its splat against the program's two,
a rehearsal of the driver ``stream_sessions`` through the harness, and the
files of the two cells this PR adds. Nothing here is a speed.
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

from benchmark import harness, traffic_gen  # noqa: E402
from benchmark.reference.raft import Reference  # noqa: E402
from benchmark.reference.raft_stream import forward_interpolate, reference_session  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
HW, ITERS = (92, 128), 4
TOL_PX = 1e-3  # CPU float32 sits near 1e-6 px; a dropped warm start reads whole pixels


def _mean_gap(a, b) -> float:
    return float(np.sqrt(((np.asarray(a) - np.asarray(b)) ** 2).sum(-1)).mean())


# ----------------------------------------------- engine against the reference


@pytest.fixture(scope="module", params=["raft_nc_dbl-sintel-warm", "raft-sintel"])
def played(request):
    """Three interleaved streams of 4 pairs through one ``StreamEngine``; then
    stream 0 closes and a new stream takes its slot. Returns the frames and
    the engine's answers by stream, with the reference and its variables."""
    from benchmark.drivers import stream_sessions
    from benchmark.program import build_model
    from raft_ncup_tpu.config import StreamConfig
    from raft_ncup_tpu.observability import Telemetry
    from raft_ncup_tpu.streaming import StreamEngine

    model = harness.load_json(os.path.join(ROOT, "benchmark", "configs", request.param + ".json"))["model"]
    ref = Reference(model)
    variables = ref.init_variables(2**31 + 11)
    rng = np.random.default_rng(5)
    clips = {name: stream_sessions.make_clip(rng, HW, 5, 6.0) for name in ("a", "b", "c", "d")}
    engine = StreamEngine(
        build_model(model), variables,
        StreamConfig(capacity=3, frame_hw=HW, iters=ITERS, batch_sizes=(1, 2, 4)),
        telemetry=Telemetry(),  # the stage counts of this engine alone
    )
    answers = {name: [] for name in clips}
    try:
        for t in range(4):  # a, b, c interleaved: one pair of each per round
            handles = {n: engine.submit(n, clips[n][t], clips[n][t + 1]) for n in "abc"}
            for n, h in handles.items():
                answers[n].append(h.result(120))
        slot_a = engine.registry.get("a").slot
        assert engine.close_stream("a")
        for t in range(2):  # d takes a's slot; its first pair must be cold
            answers["d"].append(engine.submit("d", clips["d"][t], clips["d"][t + 1]).result(120))
        assert engine.registry.get("d").slot == slot_a
        report = engine.report()
    finally:
        engine.drain(timeout=120)
    return {"ref": ref, "variables": variables, "clips": clips, "answers": answers, "report": report}


def test_engine_sessions_agree_with_the_reference(played):
    for name, frames in played["clips"].items():
        got = played["answers"][name]
        want = reference_session(played["ref"], played["variables"], frames[: len(got) + 1], ITERS)
        assert len(got) == len(want) >= 2
        for a, w in zip(got, want):
            assert a.ok and a.flow.shape == HW + (2,)
            assert _mean_gap(a.flow, w) < TOL_PX  # cold first, warm after, a reused slot too


def test_dropping_the_warm_start_is_seen(played):
    """The control of the cell's limit: the reference with every pair cold
    differs from the engine's warm answers by far more than the tolerance,
    and agrees with it on the cold one."""
    frames = played["clips"]["b"]
    got = played["answers"]["b"]
    cold = reference_session(played["ref"], played["variables"], frames, ITERS, warm_start=False)
    assert _mean_gap(got[0].flow, cold[0]) < TOL_PX
    assert all(_mean_gap(a.flow, c) > 100 * TOL_PX for a, c in zip(got[1:], cold[1:]))


def test_report_counts_what_was_played(played):
    c = played["report"]["counters"]
    assert c["stream_frames_completed_total"] == c["stream_frames_accepted_total"] == 14
    assert c["stream_frames_cold_start_total"] == c["stream_streams_opened_total"] == 4
    assert c["stream_streams_closed_total"] == 1 and c["stream_slots_reset_total"] == 0
    stages = played["report"]["stages"]
    for name in ("stream_batch_assembly", "stream_pad_stage", "stream_dispatch",
                 "stream_throttle_wait", "stream_device_wait", "stream_pull", "stream_deliver"):
        assert stages[name]["count"] == c["stream_batches_total"], name
    assert stages["stream_queue_wait"]["count"] == 14
    (program,) = played["report"]["executable_memory"][-1:]
    assert "'stream'" in program["key"] and program["temp_size_in_bytes"] > 0


# ------------------------------------------------------------------ the splat


def _flows():
    g = np.random.default_rng(0)
    return {
        "dense": g.normal(0, 1.5, (20, 31, 2)).astype(np.float32),
        "sparse_survivors": g.normal(0, 60.0, (16, 16, 2)).astype(np.float32),
        "nothing_survives": np.full((8, 8, 2), 1000.0, np.float32),
        "leaving_the_image": np.concatenate(
            [g.normal(0, 2.0, (12, 9, 2)), g.normal(0, 40.0, (12, 9, 2))], axis=1
        ).astype(np.float32),
    }


@pytest.mark.parametrize("case", sorted(_flows()))
def test_reference_splat_equals_the_programs_two(case):
    import jax.numpy as jnp

    from raft_ncup_tpu.ops import warmstart

    flow = _flows()[case]
    mine = np.asarray(forward_interpolate(flow))
    np.testing.assert_array_equal(mine, warmstart.forward_interpolate(flow))  # host k-d tree
    np.testing.assert_array_equal(mine, np.asarray(warmstart.forward_interpolate_jax(jnp.asarray(flow))))
    if case == "nothing_survives":
        assert (mine == 0).all()
    else:
        assert np.abs(mine).max() > 0


# ------------------------------------------------- the driver, through a run


def toy_tree(tmp_path) -> str:
    """A checkout-like tree whose one cell ``toy`` is ``stream_sintel_nc`` at a
    toy size: another traffic file, configuration file and limits file, found
    by name like every cell's."""
    root = str(tmp_path / "tree")
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    base = os.path.join(root, "benchmark")
    t = harness.load_json(os.path.join(base, "traffic", "stream_closed24.json"))
    t.update({"native_hw": list(HW), "players": 6, "clips": 3, "clip_frames": 8, "session_pairs": [3, 6]})
    c = harness.load_json(os.path.join(base, "configs", "raft_nc_dbl-sintel-warm.json"))
    c["stream"].update({"frame_hw": list(HW), "iters": ITERS, "batch_sizes": [2], "capacity": 8})
    for sub, body in (("traffic", t), ("configs", c), ("limits", {"limits": {"flow_gap_median_px": TOL_PX, "flow_gap_mean_px": TOL_PX}})):
        with open(os.path.join(base, sub, "toy.json"), "w") as f:
            json.dump(body, f)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "toy", "file": "benchmark/configs/toy.json"})
    bench["workloads"] = [{"name": "toy", "config": "toy", "traffic": "toy", "chips": 1, "why": "toy"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["toy"] if "stream_sintel_nc" in m["workloads"] else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def drive(root: str, trace: int = 0) -> dict:
    return harness.run_cell(
        "toy", 2**31 + 7, 1.0, trace, t_start=time.perf_counter(), root=root, require_tpu=False,
    )


def test_stream_cell_toy_run_is_correct_and_shows_the_mechanism(tmp_path, capsys):
    res = drive(toy_tree(tmp_path))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"pairs_per_s", "setup_s"}  # no latency_p95_ms: 24 / rate
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert lines[-1] == res
    window = next(x for x in lines if x.get("phase") == "window")
    c = window["counters"]
    assert c["cold"] == window["sessions_begun"] > 0 and window["sessions_ended"] > 0
    assert c["pairs"] == c["completed"] == res["attempted"] and c["padded_rows"] == 0
    assert window["executable_memory"] and window["executables"]["compiles"] == 1
    checks = {x["check"]: x for x in lines if "check" in x}
    assert checks["flow_gap_median_px"]["value"] <= checks["flow_gap_mean_px"]["value"] < TOL_PX
    assert checks["flow_gap_cold_median_px"]["value"] < TOL_PX
    assert -checks["warm_start_dropped_gap_px_negated"]["value"] > 100 * TOL_PX
    reference = next(x for x in lines if x.get("phase") == "reference")
    assert len(reference["flow_gap_warm_px"]) == 4 and len(reference["flow_gap_cold_px"]) == 2
    assert set(reference["flow_gap_warm_px"][0]) == {"mean", "median", "p90"}


def test_warm_pairs_served_cold_are_not_correct(tmp_path, monkeypatch):
    """The timed path broken where the mechanism lives: the splat hands back
    zeros, so every warm pair starts cold. Nothing fails, nothing is slower,
    and every counter is as before: only the comparison can see it."""
    import jax.numpy as jnp

    from raft_ncup_tpu.ops import warmstart

    monkeypatch.setattr(warmstart, "forward_interpolate_batch", lambda flow, chunk=1024: jnp.zeros_like(flow))
    res = drive(toy_tree(tmp_path))
    assert res["correct"] is False and res["failed"] == 0


def test_traced_run_reads_the_stream_metrics(tmp_path, monkeypatch):
    from benchmark import trace_reduce

    monkeypatch.setattr(
        trace_reduce, "reduce_trace_dir",
        lambda d: {"busy_s": 0.5, "window_s": 1.0, "layout": {},
                   "device_ops": [["fusion.1", 0.4]], "idle_gaps": [["bench.player_wait", 0.1]]},
    )
    res = drive(toy_tree(tmp_path), trace=1)
    want = {m["name"] for m in harness.metrics_of(BENCH["per_layer"], "stream_sintel_nc")}
    assert set(res["metrics"]) == want and len(want) == 11
    assert res["metrics"]["stream_padded_rows_pct"]["value"] == 0.0
    assert 0.0 < res["metrics"]["stream_cold_start_pct"]["value"] < 100.0
    assert res["metrics"]["stream_throttle_wait_p50_ms"]["value"] is not None


@pytest.mark.parametrize("reader", sorted(
    m["name"] for m in BENCH["per_layer"] if m.get("workloads") == ["stream_sintel_nc"]
))
def test_stream_readers_find_nothing_in_a_program_without_the_spans(reader):
    """The parent's report has neither the new stages nor ``counters``: the
    reader leaves the metric out and does not raise."""
    mod = harness.load_module(os.path.join(ROOT, "benchmark", "layer_metrics", reader + ".py"))
    assert mod.read({"report": {"stages": {}}, "window": {}, "setup": {}}) is None
    assert mod.read({"report": {}, "window": {}, "setup": {}}) is None


def test_a_program_without_the_counters_is_refused_at_once(tmp_path, monkeypatch):
    """What the parent commit does with this driver laid over it: exit code 2
    before anything is compiled, not a hang."""
    from raft_ncup_tpu.streaming import StreamEngine

    sound = StreamEngine.report
    monkeypatch.setattr(
        StreamEngine, "report",
        lambda self: {k: v for k, v in sound(self).items() if k != "counters"},
    )
    with pytest.raises(harness.NoResult):
        drive(toy_tree(tmp_path))


# ------------------------------------------------------------- files and data


@pytest.mark.parametrize("cell, config, driver", [
    ("eval_sintel_raft", "raft-sintel", "eval_pass"),
    ("stream_sintel_nc", "raft_nc_dbl-sintel-warm", "stream_sessions"),
])
def test_new_cells_resolve_their_files(cell, config, driver):
    c = harness.Cell(ROOT, BENCH, cell, 3)
    assert c.workload["config"] == config and c.traffic["driver"] == driver
    tight = "flow_gap_median_px" if driver == "stream_sessions" else "flow_gap_mean_px"
    assert c.workload["chips"] == 1 and c.limit("flow_gap_mean_px") >= c.limit(tight) > 0
    assert c.config["reduced"] == [] and c.config["runtime"] == {"jax_default_matmul_precision": "highest"}
    reported = {m["name"] for m in harness.metrics_of(BENCH["end_to_end"], cell)}
    assert reported == {"pairs_per_s", "setup_s"}
    layers = {m["name"] for m in harness.metrics_of(BENCH["per_layer"], cell)}
    assert {"compile_s", "device_ms_per_pair", "device_idle_pct.infer"} <= layers
    readings = harness.load_json(os.path.join(ROOT, "benchmark", "limits", cell + ".json"))["readings"]
    assert readings["program_largest"] < c.limit(tight) < readings["control_high_smallest"]


def test_warm_configuration_is_the_flagships_model_with_the_protocol():
    warm = harness.load_json(os.path.join(ROOT, "benchmark", "configs", "raft_nc_dbl-sintel-warm.json"))
    flagship = harness.load_json(os.path.join(ROOT, "benchmark", "configs", "raft_nc_dbl-sintel.json"))
    assert warm["model"] == flagship["model"] and warm["widths"] == flagship["widths"]
    assert warm["stream"] == {"frame_hw": [436, 1024], "iters": 32, "batch_sizes": [8],
                              "capacity": 32, "max_frame_gap": 1, "carry_net": False}
    assert warm["protocol"]["iters"] == 32 and len(warm["source"]) <= 200
    limits = harness.load_json(os.path.join(ROOT, "benchmark", "limits", "stream_sintel_nc.json"))
    loose = limits["limits"]["flow_gap_mean_px"]
    assert limits["readings"]["mean_program_largest"] < loose < limits["readings"]["warm_start_dropped_smallest"]


def test_clips_are_the_same_video_from_every_seed():
    from benchmark.drivers import stream_sessions

    t = {"native_hw": [40, 64], "clips": 2, "clip_frames": 5, "max_flow_px": 5.0}
    a, b, c = (stream_sessions.make_clips(t, s) for s in (2**31 + 9, 2**31 + 9, 4))
    assert all(np.array_equal(x, y) for ca, cb in zip(a, b) for x, y in zip(ca, cb))
    assert not np.array_equal(a[0][0], c[0][0])
    assert {f.shape for clip in a for f in clip} == {(40, 64, 3)} and a[0][0].dtype == np.uint8
    assert [len(clip) for clip in a] == [5, 5]
    # consecutive frames differ (the clip moves) and the first two are make_pair's
    assert all(not np.array_equal(x, y) for x, y in zip(a[0], a[0][1:]))
    pair = traffic_gen.make_pair(
        np.random.default_rng(np.random.SeedSequence([2**31 + 9, 0x636C6970])), (40, 64), 5.0)
    assert np.array_equal(a[0][0], pair["image1"]) and np.array_equal(a[0][1], pair["image2"])
