"""The benchmark's own tests: all on the CPU at toy sizes. They rehearse the
control flow and the arithmetic of ``benchmark/``; nothing here is a speed.

The toy tree is a copy of ``benchmark/`` in a temp directory with one more
traffic file, one more limits file, one more reader and a ``BENCHMARK.json``
that names them: the harness finds every one of them by name, which is how a
later PR adds a cell without editing a file that is there.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, harness, meters, trace_reduce, traffic_gen  # noqa: E402
from benchmark.reference.raft import Reference, reference_flow  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
TOY = {
    "eval_pass": ("eval_sintel", "raft_nc_dbl-sintel",
                  {"native_hw": [92, 128], "iters": 4, "batch_size": 2, "pool": 4,
                   "pairs_per_pass": 4}),
    "serve_closed": ("serve_closed24", "raft-sintel",
                     {"native_hw": [92, 128], "iter_levels": [4], "pool": 4, "clients": 6,
                      "batch_sizes": [2]}),
}
TOY_LIMIT_PX = 1e-3  # CPU f32 sits near 1e-6 px, bf16_infer near 1e-2 px
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def toy_tree(tmp_path, driver: str, precision: str | None = None) -> str:
    """A checkout-like tree whose one cell ``toy`` runs ``driver`` at a toy
    size, with one new per-layer metric ``toy_passes`` read by a new file
    and, with ``precision``, a new configuration ``toy`` whose model section
    names another of the program's precision presets."""
    root = str(tmp_path / "tree")
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    traffic, config, small = TOY[driver]
    t = harness.load_json(os.path.join(root, "benchmark", "traffic", traffic + ".json"))
    t.update(small)
    with open(os.path.join(root, "benchmark", "traffic", "toy.json"), "w") as f:
        json.dump(t, f)
    with open(os.path.join(root, "benchmark", "limits", "toy.json"), "w") as f:
        json.dump({"limits": {"flow_gap_mean_px": TOY_LIMIT_PX}}, f)
    with open(os.path.join(root, "benchmark", "layer_metrics", "toy_passes.py"), "w") as f:
        f.write("def read(run):\n    return run['window'].get('pairs')\n")
    bench = json.loads(json.dumps(BENCH))
    real = next(w["name"] for w in BENCH["workloads"] if w["config"] == config)
    if precision:
        c = harness.load_json(os.path.join(root, "benchmark", "configs", config + ".json"))
        c["model"]["precision"] = precision
        with open(os.path.join(root, "benchmark", "configs", "toy.json"), "w") as f:
            json.dump(c, f)
        bench["configs"].append({**bench["configs"][0], "name": "toy", "file": "benchmark/configs/toy.json"})
        config = "toy"
    bench["workloads"] = [
        {"name": "toy", "config": config, "traffic": "toy", "chips": 1, "why": "toy"}
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["toy"] if real in m["workloads"] else []
    bench["per_layer"].append(
        {"name": "toy_passes", "unit": "pairs", "better": "higher",
         "source": "program_counter", "layer": "entry points", "moves": "setup_s",
         "workloads": ["toy"]}
    )
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def drive(root: str, trace: int = 0) -> dict:
    return harness.run_cell(
        "toy", 2**31 + 7, 1.0, trace, t_start=time.perf_counter(), root=root,
        require_tpu=False,
    )


# ------------------------------------------------------------ BENCHMARK.json


def test_names_units_and_files():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert name.match(m["name"]) and unit.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    for w in BENCH["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = harness.Cell(ROOT, BENCH, w["name"], 1)  # every file resolves
        assert callable(cell.driver.setup) and callable(cell.driver.check)
        assert "flow_gap_mean_px" in cell.limits
        reported = {m["name"] for m in harness.metrics_of(BENCH["end_to_end"], w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.metrics_of(BENCH["per_layer"], w["name"])
    for c in BENCH["configs"]:
        assert name.match(c["name"]) and c["file"].startswith("benchmark/")
        assert harness.load_json(os.path.join(ROOT, c["file"]))["reduced"] == c["reduced"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51


# ------------------------------------------------- the drivers, through a run


@pytest.mark.parametrize("driver", sorted(TOY))
def test_driver_toy_run_is_correct_and_has_the_contract_keys(tmp_path, driver, capsys):
    res = drive(toy_tree(tmp_path, driver))
    assert set(res) == RESULT_KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    real = {"eval_pass": "eval_sintel_nc", "serve_closed": "serve_sintel_raft"}[driver]
    want = {m["name"] for m in harness.metrics_of(BENCH["end_to_end"], real)}
    assert set(res["metrics"]) == want
    assert all(set(v) == {"value", "unit"} for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == res
    compared = [json.loads(x) for x in lines if x.startswith('{"check"')]
    assert len(compared) >= 4
    assert all({"check", "value", "limit", "ok"} <= set(c) for c in compared)


@pytest.mark.parametrize("driver", sorted(TOY))
def test_broken_timed_path_is_not_correct(tmp_path, driver, monkeypatch):
    """The answer altered where it is produced: half a pixel added to the
    program's upsampled flow. The rest of the run is untouched."""
    from raft_ncup_tpu.models.raft import RAFT

    sound = RAFT._upsample
    monkeypatch.setattr(
        RAFT, "_upsample", lambda self, *a, **k: sound(self, *a, **k) + 0.5
    )
    res = drive(toy_tree(tmp_path, driver))
    assert res["correct"] is False and res["failed"] == 0


@pytest.mark.parametrize("driver", sorted(TOY))
def test_lower_precision_is_not_correct(tmp_path, driver):
    """The control at a size a test run holds: the program with its own
    lower-precision path, ``bf16_infer``, switched on through a configuration
    file. The cells' control on the chip is the reference at ``high`` (three
    bf16 passes), which a CPU computes in float32 and so cannot show; its
    chip readings, and ``bf16_infer``'s, are in PERF.md section 2."""
    res = drive(toy_tree(tmp_path, driver, precision="bf16_infer"))
    assert res["correct"] is False and res["failed"] == 0


@pytest.mark.parametrize("driver", sorted(TOY))
def test_control_reads_the_number_the_check_compares(tmp_path, driver):
    """``control(cell)`` puts the reference at the control precision in the
    program's place; on a CPU both precisions are float32, so it reads 0."""
    root = toy_tree(tmp_path, driver)
    cell = harness.Cell(root, harness.load_json(os.path.join(root, "BENCHMARK.json")), "toy", 2**31 + 7)
    assert cell.config["control"]["reference_precision"] == "high"
    assert cell.config["runtime"] == {"jax_default_matmul_precision": "highest"}
    (row,) = cell.driver.control(cell)
    assert row["check"] == "flow_gap_mean_px" and row["value"] == 0.0


def test_traced_run_reads_layer_metrics_from_files(tmp_path, monkeypatch):
    """A per-layer metric added as one reader file and one entry is reported;
    the trace reduction is stubbed because a CPU trace has no device plane."""
    monkeypatch.setattr(
        trace_reduce, "reduce_trace_dir",
        lambda d: {"busy_s": 0.5, "window_s": 1.0, "layout": {},
                   "device_ops": [["fusion.1", 0.4]], "idle_gaps": [["bench.eval_pass", 0.1]]},
    )
    res = drive(toy_tree(tmp_path, "eval_pass"), trace=1)
    assert set(res) == RESULT_KEYS | {"breakdown"}
    assert res["metrics"]["toy_passes"]["value"] == res["attempted"]
    assert res["metrics"]["device_idle_pct.infer"]["value"] == pytest.approx(50.0)
    assert "pairs_per_s" not in res["metrics"] and "compile_s" in res["metrics"]
    assert "serve_drain_p50_ms" not in res["metrics"]  # another cell's
    assert res["device"]["busy_s"] == 0.5 and res["device"]["window_s"] == 1.0


def test_run_py_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


# ------------------------------------------------------------- the reference


@pytest.mark.parametrize("config", ["raft_nc_dbl-sintel", "raft-sintel"])
def test_reference_agrees_with_the_program_and_owns_the_weights(config):
    import jax
    import jax.numpy as jnp

    from benchmark.program import build_model

    model = harness.load_json(os.path.join(ROOT, "benchmark", "configs", config + ".json"))["model"]
    ref = Reference(model)
    variables = ref.init_variables(2**31 + 5)
    again = ref.init_variables(2**31 + 5)
    other = ref.init_variables(6)
    leaves = jax.tree_util.tree_leaves
    assert all(np.array_equal(a, b) for a, b in zip(leaves(variables), leaves(again)))
    assert any(not np.array_equal(a, b) for a, b in zip(leaves(variables), leaves(other)))

    program = build_model(model)
    shapes = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), t)  # noqa: E731
    want = jax.eval_shape(lambda k: program.init(k, (1, 64, 64, 3)), jax.random.key(0))
    assert shapes(variables) == shapes(want)  # the checkpoint layout the program loads

    pair = traffic_gen.make_pair(np.random.default_rng(3), (92, 128), 6.0)
    flow = reference_flow(ref, variables, pair["image1"], pair["image2"], 4)
    assert flow.shape == (92, 128, 2) and np.isfinite(flow).all()
    i1 = jnp.asarray(np.pad(pair["image1"], ((2, 2), (0, 0), (0, 0)), mode="edge"), jnp.float32)[None]
    i2 = jnp.asarray(np.pad(pair["image2"], ((2, 2), (0, 0), (0, 0)), mode="edge"), jnp.float32)[None]

    def gap(precision):
        with jax.default_matmul_precision("highest"):
            _, up = build_model({**model, "precision": precision}).apply(
                variables, i1, i2, iters=4, test_mode=True)
        return float(np.sqrt(((np.asarray(up)[0, 2:-2] - flow) ** 2).sum(-1)).mean())

    assert gap("f32") < 1e-4  # same mathematics, float32
    assert gap("bf16_infer") > TOY_LIMIT_PX  # the lower precision is seen


# ------------------------------------------------------- yardstick arithmetic


def test_trace_reduction_on_handmade_intervals():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.busy_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.gaps([(1, 3), (5, 6)], 0, 10) == [(0, 1), (3, 5), (6, 10)]
    ops = {"/device:TPU:0": [("while", 1.0, 3.0), ("fusion.1", 1.0, 2.0),
                             ("fusion.1", 2.0, 2.5), ("copy", 5.0, 6.0)]}
    spans = [("bench.window", 0.0, 10.0), ("bench.eval_pass", 0.5, 4.5),
             ("bench.eval_pass", 4.6, 9.0), ("other", 0.0, 10.0)]
    out = trace_reduce.reduce(ops, [s for s in spans if s[0].startswith("bench.")])
    assert out["busy_s"] == pytest.approx(3.0) and out["window_s"] == pytest.approx(10.0)
    assert out["device_ops"][0] == ["while", 2.0] and out["device_ops"][1] == ["fusion.1", 1.5]
    assert out["idle_gaps"][0] == ["bench.eval_pass", pytest.approx(4.0)]  # 6..10
    assert [g[1] for g in out["idle_gaps"]] == sorted((g[1] for g in out["idle_gaps"]), reverse=True)
    with pytest.raises(ValueError):
        trace_reduce.reduce({"/device:TPU:0": []}, spans)


def test_trace_reader_finds_the_benchmarks_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    _, spans, layout = trace_reduce.read_xplane(trace_reduce.find_xplane(str(tmp_path)))
    assert [s[0] for s in spans] == ["bench.window"] and spans[0][2] > spans[0][1]
    assert any(p.startswith("/host:") for p in layout)


def test_nearest_rank_and_peaks():
    xs = [0.1 * i for i in range(1, 17)]
    assert meters.nearest_rank(xs, 0.5) == pytest.approx(0.8)  # the 8th of 16
    assert meters.nearest_rank(xs, 0.95) == pytest.approx(1.6)
    assert meters.nearest_rank([3.0], 0.95) == 3.0 and meters.nearest_rank([], 0.5) is None
    assert meters.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        meters.load_peaks("cpu")


def test_flops_count_every_iteration():
    nc = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft_nc_dbl-sintel.json"))["model"]
    rf = harness.load_json(os.path.join(ROOT, "benchmark/configs/raft-sintel.json"))["model"]
    a, b = flops.forward_flops(nc, 1, 440, 1024, 12), flops.forward_flops(nc, 1, 440, 1024, 32)
    per_iter = (b - a) / 20
    assert per_iter > 2e10 and b == pytest.approx(a + 20 * per_iter)
    assert flops.forward_flops(nc, 2, 440, 1024, 32) == 2 * b
    assert flops._conv(3, 64, 64, 10, 10) == 2 * 9 * 64 * 64 * 100
    # the mask head runs in every iteration, NCUP once per inference forward
    assert flops.forward_flops(rf, 1, 440, 1024, 32) - flops.forward_flops(rf, 1, 440, 1024, 12) > 20 * per_iter
    assert flops.forward_flops(nc, 1, 368, 768, 12, upsample_every_iteration=True) > flops.forward_flops(nc, 1, 368, 768, 12)


def test_traffic_is_the_same_work_from_every_seed():
    t = {"native_hw": [40, 64], "pool": 3, "max_flow_px": 5.0}
    a, b, c = (traffic_gen.make_pool(t, s) for s in (2**31 + 9, 2**31 + 9, 4))
    assert all(np.array_equal(x["image2"], y["image2"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["image1"], c[0]["image1"])
    assert {p["image1"].shape for p in a + c} == {(40, 64, 3)}
    assert a[0]["image1"].dtype == np.uint8 and a[0]["flow"].dtype == np.float32
    assert traffic_gen.sample_indices(7, 16, 4) == traffic_gen.sample_indices(7, 16, 4)
    assert len(set(traffic_gen.sample_indices(7, 3, 8))) == 3
