"""The readers PR 24 added for the program's new spans: each returns a number
in a CPU rehearsal of its cell (a count's worth: whatever it reads as a time is
the CPU's and is never written down), and ``None`` where the program has no
such span, as the parent commit has not."""

from __future__ import annotations

import os

import pytest

import test_benchmark as tb  # the toy tree and the stubbed traced run
from benchmark import harness, trace_reduce

SERVE = ["serve_pad_stage_p50_ms", "serve_dispatch_p50_ms", "serve_throttle_wait_p50_ms",
         "serve_device_wait_p50_ms", "serve_pull_p50_ms"]
EVAL = ["eval_input_wait_ms_per_pair", "eval_input_stage_ms_per_pair",
        "eval_input_h2d_ms_per_pair"]
BEFORE = {"eval_pass": {"compile_s", "device_ms_per_pair", "device_idle_pct.infer"},
          "serve_closed": {"compile_s", "device_ms_per_pair", "device_idle_pct.infer",
                           "serve_queue_wait_p50_ms", "serve_drain_p50_ms"}}


def reader(name: str):
    return harness.load_module(
        os.path.join(tb.ROOT, "benchmark", "layer_metrics", name + ".py")
    )


@pytest.fixture
def fresh_hub():
    from raft_ncup_tpu.observability import Telemetry, set_telemetry

    hub = Telemetry()
    prev = set_telemetry(hub)
    yield hub
    set_telemetry(prev)


@pytest.mark.parametrize("driver,mine,others", [
    ("eval_pass", EVAL, SERVE), ("serve_closed", SERVE, EVAL),
])
def test_traced_rehearsal_reports_the_new_metrics_of_its_cell(
    tmp_path, monkeypatch, fresh_hub, driver, mine, others
):
    monkeypatch.setattr(
        trace_reduce, "reduce_trace_dir",
        lambda d: {"busy_s": 0.5, "window_s": 1.0, "layout": {},
                   "device_ops": [["fusion.1", 0.4]], "idle_gaps": [["input_wait", 0.1]]},
    )
    res = tb.drive(tb.toy_tree(tmp_path, driver), trace=1)
    got = res["metrics"]
    assert res["correct"] is True
    for name in mine:
        assert got[name]["unit"] == "ms" and got[name]["value"] >= 0.0, name
    assert not set(others) & set(got)
    assert BEFORE[driver] <= set(got)  # what the cell printed before PR 24
    if driver == "eval_pass":
        # totals of one hub at one boundary: every pass of the process
        pairs = fresh_hub.counter_value("eval_pairs_total")
        assert pairs >= res["attempted"] and pairs % 2 == 0
        waited = fresh_hub.registry.get("input_wait_ms")
        assert got["eval_input_wait_ms_per_pair"]["value"] == pytest.approx(
            waited.sum_ms / pairs)


@pytest.mark.parametrize("name", SERVE + EVAL)
def test_reader_finds_nothing_on_an_empty_report(fresh_hub, name):
    assert reader(name).read({"report": {}, "window": {}, "setup": {}}) is None


@pytest.mark.parametrize("name,want", [
    ("serve_dispatch_p50_ms", 10700.0), ("serve_pad_stage_p50_ms", 150.0),
    ("serve_throttle_wait_p50_ms", None), ("serve_device_wait_p50_ms", None),
    ("serve_pull_p50_ms", None),
])
def test_serve_readers_on_a_program_without_the_split(name, want):
    """What the parent commit's ``FlowServer.report()`` holds: the two spans it
    has are read, the three it lacks are left out."""
    stages = {"serve_dispatch": {"count": 3, "p50_ms": 10700.0, "p99_ms": 10900.0},
              "serve_pad_stage": {"count": 3, "p50_ms": 150.0, "p99_ms": 160.0},
              "serve_drain": {"count": 3, "p50_ms": 21500.0, "p99_ms": 21600.0}}
    assert reader(name).read({"report": {"stages": stages}, "window": {}}) == want


@pytest.mark.parametrize("name", EVAL)
def test_eval_readers_need_both_the_span_and_the_counter(fresh_hub, name):
    span = name[len("eval_"):-len("_ms_per_pair")]
    fresh_hub.observe_ms(span, 40.0)
    assert reader(name).read({"report": {}}) is None  # no pair counted yet
    fresh_hub.inc("eval_pairs_total", 8)
    fresh_hub.observe_ms(span, 40.0)
    assert reader(name).read({"report": {}}) == pytest.approx(10.0)
