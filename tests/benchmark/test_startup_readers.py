"""The readers PR 35 added for the program's start-up timeline
(``raft_ncup_tpu.observability.startup_report()``): each resolves to a file,
returns a number in a CPU rehearsal of its cell (whatever it reads as a time
is the CPU's and is never written down), ``None`` on an empty report and on a
program without the report, and the ``setup_*_s`` readers add up to the run's
``setup_s``."""

from __future__ import annotations

import os

import pytest

import test_benchmark as tb  # the toy tree and the stubbed traced run
from benchmark import harness, trace_reduce

SUMMED = ["setup_trace_lower_s", "setup_program_load_s", "setup_first_run_s",
          "setup_weights_s", "setup_input_start_s", "setup_unattributed_s"]
NEW = SUMMED + ["setup_cache_miss_programs", "eval_pass_start_p50_ms"]
# no entry yet: the training cell's metric set is pinned by an accepted test
# (tests/benchmark/test_train_cell.py), so the next benchmark PR lists it; as
# it does train_sintel_nc, stream_sintel_nc and eval_1080p_nc for the others
# (test_stream_cell.py and test_1080p_cell.py pin theirs too).
WITHOUT_ENTRY = {"setup_weights_s"}
EVAL_ONLY = {"setup_input_start_s", "eval_pass_start_p50_ms"}


def reader(name: str):
    return harness.load_module(
        os.path.join(tb.ROOT, "benchmark", "layer_metrics", name + ".py")
    )


@pytest.fixture
def fresh():
    """A hub and a start-up record of the test's own."""
    from raft_ncup_tpu.observability import (
        StartupRecord,
        Telemetry,
        set_startup_record,
        set_telemetry,
    )

    hub, record = Telemetry(), StartupRecord()
    prev_hub, prev_record = set_telemetry(hub), set_startup_record(record)
    yield hub, record
    set_telemetry(prev_hub)
    set_startup_record(prev_record)


@pytest.mark.parametrize("name", NEW)
def test_new_entries_resolve_to_files_in_their_cells(name):
    assert callable(reader(name).read)
    entries = [m for m in tb.BENCH["per_layer"] if m["name"] == name]
    if name in WITHOUT_ENTRY:
        assert entries == []
        return
    (m,) = entries
    assert m["layer"] == "entry points" and m["better"] == "lower"
    assert m["moves"] == ("pairs_per_s" if name == "eval_pass_start_p50_ms" else "setup_s")
    assert m["source"] == ("program_counter" if name == "setup_cache_miss_programs" else "program_span")
    evals = ["eval_sintel_nc", "eval_sintel_raft"]
    want = evals if name in EVAL_ONLY else ["eval_sintel_nc", "serve_sintel_raft", "eval_sintel_raft"]
    assert m["workloads"] == want
    # appended: nothing that was there moved
    assert tb.BENCH["per_layer"].index(m) >= len(tb.BENCH["per_layer"]) - 7


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_on_an_empty_report(fresh, name):
    assert reader(name).read({"report": {}, "window": {}, "setup": {"setup_s": 30.0}}) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_report(fresh, name, monkeypatch):
    """The parent commit: ``observability`` has no ``startup_report`` and the
    hub no ``input_start`` span; the reader leaves the metric out and does
    not raise."""
    import raft_ncup_tpu.observability as obs

    monkeypatch.delattr(obs, "startup_report")
    assert reader(name).read({"report": {}, "window": {}, "setup": {"setup_s": 30.0}}) is None


def test_setup_readers_add_up_to_the_runs_setup_s(fresh):
    _, record = fresh
    totals = {"programs_loaded": 9, "cache_hits": 6, "cache_misses": 3, "compile_s": 70.5}
    record.program("metrics", "metrics", trace_lower_s=3.25, compile_s=64.5, cache="miss",
                   process=totals)
    record.first_run("metrics", 0.125)
    record.program("forward", "forward", trace_lower_s=1.5, compile_s=2.0, cache="hit",
                   process=totals)  # built, never called: its first run counts 0
    record.phase("weights_s", 0.75)
    record.phase("input_start_s", 0.25)
    record.phase("warmup_s", 50.0)  # the parent of the programs' phases: not a term
    run = {"report": {}, "window": {}, "setup": {"setup_s": 100.0}}
    got = {name: reader(name).read(run) for name in NEW}
    assert got["setup_trace_lower_s"] == 4.75 and got["setup_program_load_s"] == 66.5
    assert got["setup_first_run_s"] == 0.125 and got["setup_weights_s"] == 0.75
    assert got["setup_input_start_s"] == 0.25 and got["setup_cache_miss_programs"] == 3
    assert got["setup_unattributed_s"] == 100.0 - (4.75 + 66.5 + 0.125 + 0.75 + 0.25)
    assert sum(got[name] for name in SUMMED) == run["setup"]["setup_s"]
    assert got["eval_pass_start_p50_ms"] is None  # no pass ran


def test_a_phase_the_cell_does_not_have_counts_nothing(fresh):
    _, record = fresh
    record.program("forward", "forward", trace_lower_s=2.0, compile_s=1.0, cache="hit",
                   process={"programs_loaded": 1, "cache_hits": 1, "cache_misses": 0, "compile_s": 1.0})
    run = {"report": {}, "window": {}, "setup": {"setup_s": 10.0}}
    assert reader("setup_weights_s").read(run) is None
    assert reader("setup_input_start_s").read(run) is None
    assert reader("setup_unattributed_s").read(run) == 7.0
    assert reader("setup_cache_miss_programs").read(run) == 0  # a warm run reads 0, not nothing


def test_pass_start_is_the_nearest_rank_median_over_every_pass(fresh):
    hub, _ = fresh
    for ms in (900.0, 250.0, 260.0, 255.0):
        hub.observe_ms("input_start", ms)
    assert reader("eval_pass_start_p50_ms").read({}) == 255.0


@pytest.mark.parametrize("driver", ["eval_pass", "serve_closed"])
def test_traced_rehearsal_reports_the_start_up_metrics_of_its_cell(
    tmp_path, monkeypatch, fresh, driver
):
    monkeypatch.setattr(
        trace_reduce, "reduce_trace_dir",
        lambda d: {"busy_s": 0.5, "window_s": 1.0, "layout": {},
                   "device_ops": [["fusion.1", 0.4]], "idle_gaps": [["input_wait", 0.1]]},
    )
    res = tb.drive(tb.toy_tree(tmp_path, driver), trace=1)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"] is True
    want = set(NEW) - WITHOUT_ENTRY - (EVAL_ONLY if driver == "serve_closed" else set())
    assert want <= set(got) and not (set(NEW) - want) & set(got)
    for name in want:
        assert got[name] >= 0.0, name
    assert got["setup_cache_miss_programs"] == 0  # the CPU backend keeps no cache
    hub, record = fresh
    report = record.report()
    # one executable, built once, whatever the number of passes or batches
    assert [p["builds"] for p in report["programs"]] == [1]
    assert report["programs"][0]["kind"] == {"eval_pass": "metrics", "serve_closed": "forward"}[driver]
    if driver == "eval_pass":
        # warm-up, the window's passes and the check: a pipeline open each
        assert hub.registry.get("input_start_ms").count >= 3
        assert report["phases"]["input_start_s"] == got["setup_input_start_s"]
    else:
        assert report["phases"]["warmup_s"] > 0 and report["phases"]["input_start_s"] is None
