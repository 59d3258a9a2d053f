"""The start-up timeline (``observability/startup.py``): each executable
leaves one record with its three phases and a cache verdict, the process
phases are banked where no reset reaches them, and the steady state pays
nothing for any of it. Toy programs only."""

from __future__ import annotations

import threading
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import ServeConfig, StreamConfig
from raft_ncup_tpu.data import DevicePrefetcher
from raft_ncup_tpu.inference.costs import CostLedger
from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
from raft_ncup_tpu.observability import (
    StartupPhase,
    StartupRecord,
    Telemetry,
    set_startup_record,
    set_telemetry,
    startup_line,
    startup_report,
)
from raft_ncup_tpu.observability.startup import MAX_PROGRAMS
from raft_ncup_tpu.serving import FlowServer
from raft_ncup_tpu.streaming import StreamEngine
from raft_ncup_tpu.utils import profiling

STARTUP_SPANS = ("startup_trace_lower", "startup_compile", "startup_first_run")


@pytest.fixture()
def record():
    fresh = StartupRecord()
    prev = set_startup_record(fresh)
    yield fresh
    set_startup_record(prev)


@pytest.fixture()
def hub():
    tel = Telemetry()
    prev = set_telemetry(tel)
    yield tel
    set_telemetry(prev)


class _DummyModel:
    """apply()-compatible stand-in (tests/test_serving.py's rig)."""

    def apply(self, variables, image1, image2, iters=1, flow_init=None,
              test_mode=True, mesh=None, metric_head=None, **kw):
        flow_up = jnp.stack([image1[..., 0] * iters, image1[..., 1]], axis=-1)
        return image1.mean(), flow_up


class _DummyVideoModel:
    """apply()-compatible streaming stand-in (tests/test_streaming.py)."""

    cfg = SimpleNamespace(hidden_dim=4)

    def apply(self, variables, image1, image2, iters=1, flow_init=None,
              test_mode=True, return_net=False, net_init=None,
              net_warm=None, **kw):
        B, H, W, _ = image1.shape
        lr = image1[:, ::8, ::8, :2] * 0.01
        if flow_init is not None:
            lr = lr + flow_init
        up = jnp.repeat(jnp.repeat(lr, 8, axis=1), 8, axis=2)
        if return_net:
            return lr, up, jnp.full((B, H // 8, W // 8, 4), 0.5, jnp.float32)
        return lr, up


def _img(seed=0, hw=(24, 32)):
    g = np.random.default_rng(seed)
    return (g.random((*hw, 3)) * 255.0).astype(np.float32)


def _span_counts(tel, names=STARTUP_SPANS) -> dict:
    return {n: len(tel.tracer.records(n)) for n in names}


# ------------------------------------------------------------- the record


def test_record_banks_programs_phases_and_process_totals():
    rec = StartupRecord()
    totals = {"programs_loaded": 7, "cache_hits": 5, "cache_misses": 2, "compile_s": 3.5}
    rec.program("k1", "forward", trace_lower_s=1.0, compile_s=2.0, cache="miss",
                probe_s=0.1, process=totals)
    rec.first_run("k1", 0.25)
    rec.first_run("nobody", 9.0)  # a key that was never built: ignored
    rec.phase("weights_s", 0.5)
    rec.phase("weights_s", 0.25)  # summed over the process's runs
    rec.phase("input_start_s", 0.4)
    rec.phase("input_start_s", 9.9)  # the process's first only
    rec.phase("warmup_s", None)  # a discarded phase
    got = rec.report()
    assert got["programs"] == [{
        "key": "k1", "kind": "forward", "trace_lower_s": 1.0, "compile_s": 2.0,
        "cache": "miss", "first_run_s": 0.25, "probe_s": 0.1, "builds": 1,
    }]
    assert got["phases"] == {"weights_s": 0.75, "input_start_s": 0.4, "warmup_s": None}
    assert got["process"] == totals and got["dropped"] == 0
    with pytest.raises(KeyError):
        rec.phase("imports_s", 1.0)


def test_record_carries_the_folded_conv_sites_of_a_program_that_has_them():
    """``conv_forms`` (PR 48): the tally of ``nn/layers.py`` as
    ``build_and_record`` hands it over (the sites that did not stay
    ``conv_general_dilated``), lists of its own on the entry; a program
    built without the key (no thin ``Conv2d`` site) carries none."""
    rec = StartupRecord()
    folded = {
        "folded_in": ["encoder/convf1"], "folded_out": ["flow_head/conv2"], "phased_in": ["conv1"],
    }
    rec.program("k1", "forward", trace_lower_s=1.0, compile_s=2.0, cache="miss",
                conv_forms=folded)
    rec.program("k2", "custom", trace_lower_s=1.0, compile_s=2.0, cache="miss")
    first, second = rec.report()["programs"]
    assert first["conv_forms"] == folded and "conv_forms" not in second
    folded["phased_in"].append("changed after the call")
    assert rec.report()["programs"][0]["conv_forms"]["phased_in"] == ["conv1"]


def test_record_keeps_the_first_64_programs_and_counts_the_rest():
    rec = StartupRecord()
    for i in range(MAX_PROGRAMS + 1):
        rec.program(f"k{i}", "custom", trace_lower_s=0.1, compile_s=0.1, cache="off")
    rec.program("k0", "custom", trace_lower_s=0.1, compile_s=0.1, cache="hit")
    got = rec.report()
    assert len(got["programs"]) == MAX_PROGRAMS == 64 and got["dropped"] == 1
    first = got["programs"][0]
    # a key built again adds to its entry and takes the newest verdict
    assert first["builds"] == 2 and first["cache"] == "hit"
    assert first["trace_lower_s"] == pytest.approx(0.2)


def test_report_outlives_the_hubs_resets(record, hub):
    with StartupPhase(hub, "startup_weights") as phase:
        pass
    record.phase("weights_s", phase.seconds)
    record.program("k", "forward", trace_lower_s=1.0, compile_s=2.0, cache="hit")
    before = startup_report()
    assert hub.registry.get("startup_weights_ms").count == 1
    hub.registry.reset()
    assert startup_report() == before
    hub.reset()
    assert hub.tracer.records("startup_weights") == []
    assert startup_report() == before and before["phases"]["weights_s"] is not None


@pytest.mark.parametrize("tel", [
    Telemetry(enabled=False), Telemetry(clock=lambda: 42.0),
], ids=["disabled_hub", "frozen_clock"])
def test_phase_seconds_need_neither_an_enabled_hub_nor_its_clock(tel):
    with StartupPhase(tel, "startup_compile", key="k") as phase:
        phase.set(cache="off")
        threading.Event().wait(0.01)
    assert phase.seconds >= 0.01


def test_the_operators_line():
    report = {
        "programs": [
            {"trace_lower_s": 4.0, "compile_s": 2.0, "cache": "hit", "first_run_s": 1.0},
            {"trace_lower_s": 0.1, "compile_s": 0.5, "cache": "hit", "first_run_s": 0.2},
        ],
        "phases": {"weights_s": 0.3, "input_start_s": 0.4, "warmup_s": None},
        "process": {}, "dropped": 0,
    }
    assert startup_line(report) == (
        "startup: trace+lower 4.1 s, load 2.5 s (2 hit, 0 miss), first run 1.2 s, "
        "weights 0.3 s, input 0.4 s"
    )
    report["programs"][0]["cache"] = "off"
    report["dropped"] = 3
    line = startup_line(report)
    assert "(1 hit, 0 miss, 1 uncached)" in line and line.endswith("3 more programs not listed")


# ------------------------------------------------- the inference chokepoint


def test_each_executable_key_leaves_one_record_and_later_calls_leave_nothing(record):
    tel, ledger = Telemetry(), CostLedger(enabled=True)
    fwd = ShapeCachedForward(_DummyModel(), {}, telemetry=tel, cost_ledger=ledger)
    x = jnp.ones((2, 8))

    def program(name):
        return fwd.custom((name,), lambda: jax.jit(lambda a: a * 2.0 + 1.0))

    assert float(program("a")(x)[0, 0]) == 3.0
    after_first = _span_counts(tel)
    assert after_first == dict.fromkeys(STARTUP_SPANS, 1)
    (entry,) = startup_report()["programs"]
    assert entry["kind"] == "custom" and "'a'" in entry["key"] and entry["builds"] == 1
    assert entry["trace_lower_s"] > 0 and entry["compile_s"] > 0 and entry["first_run_s"] > 0
    assert entry["cache"] == "off"  # the CPU backend keeps no persistent cache
    assert "saved_residuals" not in entry  # a program that builds no checkpoint
    assert "contract_forms" not in entry  # nor looks a materialised pyramid up
    assert "conv_forms" not in entry  # nor has a ``Conv2d`` site to fold
    for _ in range(5):  # the steady path: one dict read then the program
        program("a")(x)
    assert _span_counts(tel) == after_first and len(startup_report()["programs"]) == 1

    program("b")(x)
    assert [p["builds"] for p in startup_report()["programs"]] == [1, 1]
    assert _span_counts(tel) == dict.fromkeys(STARTUP_SPANS, 2)
    compile_span = tel.tracer.records("startup_compile")[-1]["attrs"]
    assert compile_span["cache"] == "off" and compile_span["programs"] == 1
    assert compile_span["kind"] == "custom" and compile_span["key"] == ledger.keys()[-1]
    # the process totals as they stood when the last build ended
    process = startup_report()["process"]
    assert process["programs_loaded"] >= 2 and process["compile_s"] > 0


def test_compile_ms_on_the_ledger_entry_is_the_sum_of_its_two_phases(record):
    ledger = CostLedger(enabled=True)
    fwd = ShapeCachedForward(_DummyModel(), {}, telemetry=Telemetry(), cost_ledger=ledger)
    fwd.custom(("sum",), lambda: jax.jit(lambda a: a + 1))(jnp.zeros((3,)))
    (key,) = ledger.keys()
    entry = ledger.entry(key)
    assert entry["compile_ms"] == entry["trace_lower_ms"] + entry["backend_compile_ms"]
    assert entry["cache"] == "off" and entry["programs"] == 1
    assert entry["first_run_ms"] > 0 and entry["probe_ms"] >= 0
    (banked,) = startup_report()["programs"]
    assert banked["key"] == key
    assert banked["trace_lower_s"] * 1e3 == pytest.approx(entry["trace_lower_ms"])
    assert banked["first_run_s"] * 1e3 == pytest.approx(entry["first_run_ms"])


class _Lowered:
    def __init__(self, events):
        self.events = events

    def compile(self):
        for name in self.events:
            if name == "compile":
                jax.monitoring.record_event_duration_secs(
                    profiling.CompileMeter._COMPILE, 0.5)
            else:
                jax.monitoring.record_event(name)
        return "executable"


class _Jitted:
    def __init__(self, events):
        self.events = events

    def lower(self, *args):
        return _Lowered(self.events)


@pytest.mark.parametrize("events,cache,programs", [
    (["compile", profiling.CompileMeter._MISS], "miss", 1),
    ([profiling.CompileMeter._HIT, "compile"], "hit", 1),
    (["compile", "compile"], "off", 2),
    ([profiling.CompileMeter._HIT, profiling.CompileMeter._MISS, "compile"], "miss", 1),
])
def test_cache_verdict_comes_from_the_events_between_the_compile_spans_ends(
    events, cache, programs
):
    """The CPU backend keeps no persistent cache (``utils/runtime``), so the
    monitoring events a TPU compile fires are injected: an entry written is
    a miss, one read a hit, neither means no cache."""
    tel = Telemetry()
    meter = profiling.compile_meter()
    before = meter.totals()
    try:
        compiled, phases = profiling.timed_build(
            tel, _Jitted(events), (1, 2), key="k", kind="forward")
        after = meter.totals()
    finally:
        # the injected hits and misses are not the process's: a rehearsal
        # that this worker runs later reads the meter's totals, and on the
        # CPU it must read no miss (tests/benchmark/test_startup_readers.py)
        meter.hits, meter.misses = before["cache_hits"], before["cache_misses"]
    assert compiled == "executable"
    assert phases["cache"] == cache and phases["programs"] == programs
    assert phases["trace_lower_s"] >= 0 and phases["compile_s"] >= 0
    (span,) = tel.tracer.records("startup_compile")
    assert span["attrs"] == {"key": "k", "kind": "forward", "cache": cache, "programs": programs}
    (lower,) = tel.tracer.records("startup_trace_lower")
    assert lower["attrs"] == {"key": "k", "kind": "forward"}
    assert after["programs_loaded"] - before["programs_loaded"] == programs
    assert after["cache_misses"] - before["cache_misses"] == events.count(
        profiling.CompileMeter._MISS)


def test_the_compile_meter_is_one_per_process():
    assert profiling.compile_meter() is profiling.compile_meter()


# ------------------------------------------------------------ input_start


def _host_batches(n):
    return [{"image1": np.full((2, 8, 8, 3), i, np.float32)} for i in range(n)]


def test_input_start_once_per_prefetcher_and_the_record_keeps_the_first(record):
    tel = Telemetry()
    for pass_id in ("p0", "p1"):
        with DevicePrefetcher(
            iter(_host_batches(3)), depth=2, telemetry=tel, span_attrs={"pass_id": pass_id},
        ) as pf:
            assert len(list(pf)) == 3
    starts = tel.tracer.records("input_start")
    assert [r["attrs"] for r in starts] == [{"pass_id": "p0"}, {"pass_id": "p1"}]
    assert tel.registry.get("input_start_ms").count == 2
    # the first batch's stage and copy lie inside it
    first = tel.tracer.records("input_h2d")[0]
    assert starts[0]["duration_ms"] >= first["duration_ms"]
    banked = startup_report()["phases"]["input_start_s"]
    assert banked is not None and banked * 1e3 == pytest.approx(
        starts[0]["duration_ms"], abs=5.0)


def test_input_start_is_discarded_on_an_empty_iterator(record):
    tel = Telemetry()
    with DevicePrefetcher(iter([]), telemetry=tel) as pf:
        assert list(pf) == []
    assert tel.tracer.records("input_start") == []
    assert startup_report()["phases"]["input_start_s"] is None


# ------------------------------------------------- server, engine, reports

SERVE_REPORT_KEYS = {
    "stats", "budget", "budget_drops", "budget_recoveries", "budget_slo_drops",
    "budget_expected_iters", "executables", "precision", "mesh", "stages", "health",
}
STREAM_REPORT_KEYS = {
    "stats", "counters", "capacity", "occupancy", "peak_occupancy", "mean_occupancy",
    "evicted", "executables", "executable_memory", "precision", "mesh", "stages", "health",
}


def test_server_warmup_is_one_phase_and_its_report_gains_startup(record):
    tel = Telemetry()
    cfg = ServeConfig(queue_capacity=8, batch_sizes=(1, 2), iter_levels=(2,), recover_patience=2)
    srv = FlowServer(_DummyModel(), {}, cfg, telemetry=tel)
    try:
        assert srv.warmup((24, 32)) == 2
        (warm,) = tel.tracer.records("startup_warmup")
        assert warm["attrs"] == {"programs": 2}
        assert _span_counts(tel) == dict.fromkeys(STARTUP_SPANS, 2)
        assert srv.submit(_img(1), _img(2)).result(60).ok
        report = srv.report()
    finally:
        srv.drain()
    assert SERVE_REPORT_KEYS <= set(report)
    assert report["startup"] == startup_report()
    assert len(report["startup"]["programs"]) == 2
    assert {p["kind"] for p in report["startup"]["programs"]} == {"forward"}
    warmup_s = report["startup"]["phases"]["warmup_s"]
    assert warmup_s * 1e3 == pytest.approx(warm["duration_ms"], abs=5.0)
    # the parent of the per-executable phases it caused
    assert warmup_s >= sum(
        p["trace_lower_s"] + p["compile_s"] + p["first_run_s"]
        for p in report["startup"]["programs"]
    )


def test_engine_warmup_is_one_phase_and_its_report_gains_startup(record):
    tel = Telemetry()
    eng = StreamEngine(
        _DummyVideoModel(), {},
        StreamConfig(capacity=2, frame_hw=(24, 32), iters=1, batch_sizes=(1, 2), queue_capacity=8),
        telemetry=tel,
    )
    try:
        assert eng.warmup() == 2
        (warm,) = tel.tracer.records("startup_warmup")
        assert warm["attrs"] == {"programs": 2}
        assert eng.submit("s0", _img(1), _img(2)).result(60).ok
        report = eng.report()
    finally:
        eng.drain()
    assert STREAM_REPORT_KEYS <= set(report)
    assert report["startup"]["phases"]["warmup_s"] > 0
    assert [p["kind"] for p in report["startup"]["programs"]] == ["stream_step"] * 2
    assert all(p["first_run_s"] > 0 for p in report["startup"]["programs"])


def test_no_startup_span_after_warmup_in_a_guarded_steady_window(
    record, forbid_host_transfers, max_recompiles
):
    """The steady state is untouched: with tracing fully on, a warm serving
    window does no implicit pull and no compile, and records none of the
    start-up phases."""
    tel = Telemetry()
    cfg = ServeConfig(queue_capacity=8, batch_sizes=(1,), iter_levels=(2,), recover_patience=2)
    srv = FlowServer(_DummyModel(), {}, cfg, telemetry=tel)
    names = STARTUP_SPANS + ("startup_warmup",)
    try:
        srv.warmup((24, 32))
        assert srv.submit(_img(3), _img(4)).result(60).ok
        warm, banked = _span_counts(tel, names), startup_report()
        with forbid_host_transfers() as stats, max_recompiles(0):
            rs = [srv.submit(_img(10 + i), _img(20 + i)).result(60) for i in range(3)]
    finally:
        srv.drain()
    assert all(r.ok for r in rs)
    assert stats.host_transfers == 0 and stats.sanctioned_gets == 3
    assert _span_counts(tel, names) == warm == {n: 1 for n in names}
    assert startup_report() == banked


# ---------------------------------------------------------------- training


def test_train_run_banks_weights_build_first_run_and_input_start(record, hub, tmp_path):
    from raft_ncup_tpu.config import DataConfig, TrainConfig, small_model_config
    from raft_ncup_tpu.data import SyntheticFlowDataset
    from raft_ncup_tpu.inference.costs import get_cost_ledger
    from raft_ncup_tpu.training.loop import open_train_run, train_steps

    hw = (64, 64)
    train_cfg = TrainConfig(
        stage="chairs", batch_size=1, image_size=hw, iters=1, num_steps=10,
        checkpoint_dir=str(tmp_path),
    )

    def two_steps():
        run = open_train_run(
            small_model_config("raft", dataset="chairs"), train_cfg,
            DataConfig(num_workers=1), dataset=SyntheticFlowDataset(hw, length=4),
        )
        try:
            train_steps(run, lambda i: i >= 2)
        finally:
            run.close()

    two_steps()
    names = STARTUP_SPANS + ("startup_weights", "input_start")
    assert _span_counts(hub, names) == {n: 1 for n in names}
    (weights,) = hub.tracer.records("startup_weights")
    assert weights["attrs"]["bytes"] > 0
    got = startup_report()
    (step,) = got["programs"]
    assert step["kind"] == "train_step" and "train_step|chairs|1x64x64|1" in step["key"]
    assert step["trace_lower_s"] > 0 and step["compile_s"] > 0 and step["first_run_s"] > 0
    # what the loop's checkpoint kept, by name (utils/remat.py): the lookup's
    # planes; 0 of the weights net's, which the baseline's head does not have
    assert step["saved_residuals"] == {"raft.corr_lookup.out": 1, "ncup.weights_net.conv": 0}
    # the form and stored dtype of each level of its lookup (ops/corr.py)
    assert step["contract_forms"] == {
        f"level{lvl}": "multiply_reduce/float32" for lvl in range(4)
    }
    # the ``Conv2d`` sites that did not stay the convolution they were
    # (nn/layers.py): the motion encoder's 7x7 over the flow and the small
    # encoder's 8-channel bottlenecks folded, the flow head folded, and both
    # encoders' stride-2 stem ``conv1`` over its phases (PR 48)
    assert step["conv_forms"] == {
        "folded_in": ["encoder/convf1", "layer1_0/conv2", "layer1_1/conv2"],
        "folded_out": ["flow_head/conv2"],
        "phased_in": ["conv1"],
    }
    assert got["phases"]["weights_s"] > 0 and got["phases"]["input_start_s"] > 0
    entry = get_cost_ledger().entry(step["key"])
    assert entry["compile_ms"] == entry["trace_lower_ms"] + entry["backend_compile_ms"]
    first_input = got["phases"]["input_start_s"]

    # a second run in the process finds the step's executable: no build, no
    # first run; its state and its input pipeline are new
    two_steps()
    assert _span_counts(hub, names) == {
        **dict.fromkeys(STARTUP_SPANS, 1), "startup_weights": 2, "input_start": 2,
    }
    again = startup_report()
    assert again["programs"] == got["programs"]
    assert again["phases"]["weights_s"] > got["phases"]["weights_s"]
    assert again["phases"]["input_start_s"] == first_input
