"""Unified telemetry subsystem (raft_ncup_tpu/observability/;
docs/OBSERVABILITY.md): registry thread-safety, histogram percentile
parity with the shared nearest-rank discipline, span correlation through
a real FlowServer batch, report() back-compat keys (pinned alias table),
the bounded export sinks, and the platform invariant — a steady-state
serving window stays sync-free and recompile-free with tracing FULLY
enabled.
"""

import json
import os
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import ServeConfig, StreamConfig, small_model_config
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.observability import (
    DEGRADED,
    DRAINING,
    HALTED,
    READY,
    STARTING,
    STATE_CODES,
    WARMING,
    FlightRecorder,
    HealthTracker,
    JsonlSink,
    LEGACY_KEY_ALIASES,
    MetricsRegistry,
    PeriodicSnapshot,
    SloEngine,
    SloSpec,
    SpanTracer,
    Telemetry,
    host_number,
    load_dump,
    match_records,
    overall_state,
    serve_slos,
    stream_slos,
    telemetry_report,
    write_healthz,
)
from raft_ncup_tpu.observability.telemetry import Histogram
from raft_ncup_tpu.serving import AdmissionQueue, FlowServer
from raft_ncup_tpu.serving.request import (
    STATUS_OK,
    FlowRequest,
    ServeStats,
    nearest_rank_ms,
)
from raft_ncup_tpu.streaming import StreamEngine
from raft_ncup_tpu.streaming.engine import StreamStats


# ------------------------------------------------------------- test rigs


class _DummyModel:
    """apply()-compatible stand-in (tests/test_serving.py's rig)."""

    def apply(self, variables, image1, image2, iters=1, flow_init=None,
              test_mode=True, mesh=None, metric_head=None, **kw):
        flow_up = jnp.stack(
            [image1[..., 0] * iters, image1[..., 1]], axis=-1
        )
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
            net = jnp.full((B, H // 8, W // 8, 4), 0.5, jnp.float32)
            return lr, up, net
        return lr, up


def _img(seed=0, hw=(24, 32)):
    g = np.random.default_rng(seed)
    return (g.random((*hw, 3)) * 255.0).astype(np.float32)


def _cfg(**kw):
    base = dict(
        queue_capacity=8, batch_sizes=(1, 2), iter_levels=(4, 2),
        recover_patience=2,
    )
    base.update(kw)
    return ServeConfig(**base)


# ------------------------------------------------------------- registry


class TestRegistry:
    def test_counter_gauge_histogram_roundtrip(self):
        reg = MetricsRegistry()
        reg.counter("a_total").inc()
        reg.counter("a_total").inc(4)
        reg.gauge("depth").set(3)
        reg.gauge("depth").set(1)
        reg.histogram("lat_ms").observe_ms(12.0)
        snap = reg.snapshot()
        assert snap["counters"]["a_total"] == 5
        assert snap["gauges"]["depth"] == {"value": 1.0, "peak": 3.0}
        assert snap["histograms"]["lat_ms"]["count"] == 1
        assert json.loads(json.dumps(snap)) == snap  # JSON-able

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_counter_rejects_negative(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("x").inc(-1)

    def test_thread_safety_no_lost_updates(self):
        """The accounting-under-concurrency property the registry exists
        for: N threads x M increments lose nothing."""
        reg = MetricsRegistry()
        n_threads, per_thread = 8, 500

        def work():
            c = reg.counter("hits_total")
            h = reg.histogram("work_ms")
            for i in range(per_thread):
                c.inc()
                h.observe_ms(float(i % 50))

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.counter("hits_total").value == n_threads * per_thread
        assert reg.histogram("work_ms").count == n_threads * per_thread

    def test_rejects_jax_typed_values_without_converting(self):
        """The no-added-sync contract at runtime: anything device-side
        is refused BEFORE conversion (float() on a device array is the
        sync). Pinned against a REAL concrete array (whose type lives
        under jaxlib, not jax) AND a jax-module stand-in (tracers)."""
        real = jnp.float32(3.5)  # type module: jaxlib.xla_extension
        with pytest.raises(TypeError, match="device sync"):
            host_number(real)
        fake = type("Tracer", (), {"__module__": "jax._src.array"})()
        with pytest.raises(TypeError, match="device sync"):
            host_number(fake)
        reg = MetricsRegistry()
        for bad in (real, fake):
            with pytest.raises(TypeError):
                reg.counter("c").inc(bad)
            with pytest.raises(TypeError):
                reg.gauge("g").set(bad)
            with pytest.raises(TypeError):
                reg.histogram("h_ms").observe_ms(bad)

    def test_prometheus_text_shape(self):
        reg = MetricsRegistry()
        reg.counter("serve_requests_shed_total").inc(2)
        reg.gauge("serve_queue_depth").set(5)
        reg.histogram("serve_drain_ms").observe_ms(3.0)
        text = reg.prometheus_text()
        assert "# TYPE serve_requests_shed_total counter" in text
        assert "serve_requests_shed_total 2" in text
        assert "serve_queue_depth_peak 5" in text
        assert 'serve_drain_ms_bucket{le="+Inf"} 1' in text
        assert "serve_drain_ms_count 1" in text


class TestHistogramPercentiles:
    def test_parity_with_serving_nearest_rank_ms(self):
        """The shared percentile discipline: the histogram's nearest-rank
        over its raw-sample window must equal serving.nearest_rank_ms on
        the identical latency sample (seconds -> ms)."""
        g = np.random.default_rng(7)
        lat_s = list(g.gamma(2.0, 0.05, size=257))
        hist = Histogram("lat_ms")
        for s in lat_s:
            hist.observe_ms(s * 1000.0)
        for p in (0.5, 0.9, 0.95, 0.99):
            assert hist.percentile_ms(p) == nearest_rank_ms(lat_s, p)

    def test_empty_percentile_is_none(self):
        assert Histogram("x_ms").percentile_ms(0.5) is None

    def test_sample_window_bounds_memory(self):
        hist = Histogram("x_ms", sample_cap=10)
        for i in range(100):
            hist.observe_ms(float(i))
        # Bucket counts keep the full history, percentiles the window
        # (the most recent sample_cap observations: 90..99 ms).
        assert hist.count == 100
        assert hist.percentile_ms(0.5) == 94.0


# ----------------------------------------------------------- span tracer


class TestSpanTracer:
    def test_span_feeds_stage_histogram(self):
        t = [0.0]
        tel = Telemetry(clock=lambda: t[0])
        with tel.span("serve_dispatch", batch_id=1):
            t[0] += 0.25
        assert tel.registry.histogram("serve_dispatch_ms").count == 1
        assert tel.tracer.stage_summary()["serve_dispatch"]["p50_ms"] == 250.0

    def test_event_counts_and_correlates(self):
        tel = Telemetry()
        tel.event("stream_slot_evicted", stream_id="s1", slot=2)
        assert tel.counter_value("stream_slot_evicted_total") == 1
        (rec,) = tel.tracer.for_attr(stream_id="s1")
        assert rec["name"] == "stream_slot_evicted"

    def test_singular_key_matches_plural_list_attr(self):
        tel = Telemetry()
        tel.event("serve_dispatch_done", request_ids=[4, 5])
        assert tel.tracer.for_attr(request_id=4)
        assert not tel.tracer.for_attr(request_id=6)

    def test_ring_is_bounded_and_counts_drops(self):
        tel = Telemetry(span_capacity=4)
        for i in range(10):
            tel.event("e", i=i)
        assert len(tel.tracer.records()) == 4
        assert tel.tracer.dropped == 6
        assert [r["attrs"]["i"] for r in tel.tracer.records()] == [
            6, 7, 8, 9,
        ]

    def test_span_attrs_reject_jax_values(self):
        tel = Telemetry()
        fake = type("Arr", (), {"__module__": "jax"})()
        with pytest.raises(TypeError, match="device sync"):
            tel.event("e", value=fake)
        with pytest.raises(TypeError, match="device sync"):
            tel.event("e", value=jnp.ones(()))  # real device scalar

    def test_disabled_hub_is_inert(self):
        tel = Telemetry(enabled=False)
        tel.inc("c_total")
        tel.gauge_set("g", 1)
        tel.event("e")
        tel.observe_ms("stage", 5.0)
        with tel.span("s"):
            pass
        assert tel.registry.names() == []
        assert tel.tracer.records() == []


class _FakeAnnotator:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs the enter and
    exit of every annotation it hands out."""

    def __init__(self):
        self.log = []

    def __call__(self, name, attrs):
        log = self.log

        class _Annotation:
            def __enter__(self):
                log.append(("enter", name, dict(attrs)))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Annotation()


class TestProfilerBridge:
    """A span context also holds a profiler annotation of its name
    (utils/profiling.annotate_spans installs the real one); the factory
    is injected, so observability/ itself never imports jax."""

    def test_annotator_sees_enter_and_exit_in_span_order(self):
        fake = _FakeAnnotator()
        tel = Telemetry()
        tel.tracer.annotate = fake
        with tel.span("outer", batch_id=3):
            with tel.span("inner", batch_id=3, request_ids=[1, 2]):
                pass
        assert [e[:2] for e in fake.log] == [
            ("enter", "outer"), ("enter", "inner"),
            ("exit", "inner"), ("exit", "outer"),
        ]
        assert fake.log[0][2] == {"batch_id": 3}
        # the ring and the histograms are fed exactly as without a bridge
        assert [r["name"] for r in tel.tracer.records()] == ["inner", "outer"]
        assert tel.registry.histogram("outer_ms").count == 1

    @pytest.mark.parametrize("produce", [
        pytest.param(lambda tel: tel.observe_ms("serve_drain", 5.0, batch_id=1),
                     id="observe_ms"),
        pytest.param(lambda tel: tel.event("stream_slot_evicted", slot=2),
                     id="event"),
        pytest.param(lambda tel: tel.hist_observe("serve_e2e_ms", 5.0),
                     id="hist_observe"),
    ])
    def test_ring_only_producers_enter_no_annotation(self, produce):
        fake = _FakeAnnotator()
        tel = Telemetry()
        tel.tracer.annotate = fake
        produce(tel)
        assert fake.log == [] and tel.registry.names()

    def test_disabled_hub_enters_no_annotation(self):
        fake = _FakeAnnotator()
        tel = Telemetry(enabled=False)
        tel.tracer.annotate = fake
        with tel.span("serve_dispatch", batch_id=1) as sp:
            sp.set(rows=2)
            sp.discard()
        assert fake.log == [] and tel.tracer.records() == []

    def test_annotation_exits_when_the_body_raises(self):
        fake = _FakeAnnotator()
        tel = Telemetry()
        tel.tracer.annotate = fake
        with pytest.raises(KeyError):
            with tel.span("serve_dispatch"):
                raise KeyError("boom")
        assert [e[:2] for e in fake.log] == [
            ("enter", "serve_dispatch"), ("exit", "serve_dispatch"),
        ]
        assert tel.registry.histogram("serve_dispatch_ms").count == 1

    def test_discarded_span_leaves_no_record_but_closes_its_annotation(self):
        fake = _FakeAnnotator()
        tel = Telemetry()
        tel.tracer.annotate = fake
        with tel.span("input_wait", batch=4) as sp:
            sp.discard()
        assert tel.tracer.records() == [] and tel.registry.names() == []
        assert [e[:2] for e in fake.log] == [
            ("enter", "input_wait"), ("exit", "input_wait"),
        ]

    @pytest.mark.parametrize("owner", ["forward", "server", "prefetcher"])
    def test_jax_side_owners_install_the_real_annotation(self, owner):
        """A hub built as ``Telemetry()`` gets the bridge from whichever
        jax-side object it is handed to."""
        from raft_ncup_tpu.data import DevicePrefetcher
        from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
        from raft_ncup_tpu.utils import profiling

        tel = Telemetry()
        assert tel.tracer.annotate is None
        if owner == "forward":
            ShapeCachedForward(_DummyModel(), {}, telemetry=tel)
        elif owner == "server":
            FlowServer(_DummyModel(), {}, _cfg(), telemetry=tel).drain()
        else:
            DevicePrefetcher(iter(()), telemetry=tel).close()
        assert tel.tracer.annotate is profiling._annotation
        ann = tel.tracer.annotate("serve_dispatch", {"batch_id": 1, "ids": [1]})
        assert isinstance(ann, jax.profiler.TraceAnnotation)

    def test_spans_land_on_the_profilers_host_plane(self, tmp_path):
        """In a capture the hub's spans are host events carrying the
        mark and their scalar attributes; the runtime's own events and
        unmarked annotations are not taken for program spans."""
        from raft_ncup_tpu.utils import profiling

        tel = Telemetry()
        profiling.annotate_spans(tel)
        with profiling.trace(str(tmp_path)):
            with tel.span("input_wait", batch=0, pass_id="abcd"):
                with jax.profiler.TraceAnnotation("bench.window"):
                    jnp.ones((32, 32)).sum().block_until_ready()
            tel.observe_ms("serve_drain", 1.0)
        _, spans = profiling.read_device_trace(
            profiling.find_xplane(str(tmp_path))
        )
        assert [s[0] for s in spans] == ["input_wait"]
        assert spans[0][2] > spans[0][1]
        assert os.path.isfile(tmp_path / profiling.OP_SCOPES_FILE)
        report = profiling.device_trace_report(str(tmp_path))
        assert report["program_spans"] == {"input_wait": 1}
        assert report["devices"] == {}  # a CPU capture has no device plane

    def test_observability_package_still_imports_no_jax(self):
        from raft_ncup_tpu.analysis.lint import run_lint

        pkg = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "raft_ncup_tpu", "observability",
        )
        result = run_lint([pkg], select=["JGL010"])
        assert not result.parse_errors and result.findings == []


# ------------------------------------------- stats mirroring / aliases


class TestLegacyAliases:
    def test_every_serve_stats_field_has_a_pinned_alias(self):
        s = ServeStats()
        int_fields = [
            k for k, v in vars(s).items()
            if isinstance(v, int) and not k.startswith("_")
        ]
        assert sorted(int_fields) == sorted(LEGACY_KEY_ALIASES["serve"])

    def test_every_stream_stats_field_has_a_pinned_alias(self):
        s = StreamStats()
        int_fields = [
            k for k, v in vars(s).items()
            if isinstance(v, int) and not k.startswith("_")
        ]
        assert sorted(int_fields) == sorted(LEGACY_KEY_ALIASES["stream"])

    def test_serve_stats_mirror_values_match_legacy_fields(self):
        tel = Telemetry()
        s = ServeStats(telemetry=tel)
        s.note_submitted()
        s.note_submitted()
        s.note_accepted()
        s.note_shed()
        s.note_timeout()
        s.note_error()
        s.note_completed()
        s.note_batch(padded_rows=3)
        s.note_rejected(9, quarantine=True)
        canon = LEGACY_KEY_ALIASES["serve"]
        for legacy, name in canon.items():
            assert tel.counter_value(name) == getattr(s, legacy), legacy
        # The dispatch-time quarantine also lands as a correlated event.
        assert tel.tracer.for_attr(request_id=9)

    def test_stream_stats_mirror_values_match_legacy_fields(self):
        tel = Telemetry()
        s = StreamStats(telemetry=tel)
        s.note("submitted")
        s.note("accepted")
        s.note("shed_streams")
        s.note("padded_rows", 4)
        s.note("cold_starts")
        canon = LEGACY_KEY_ALIASES["stream"]
        for legacy, name in canon.items():
            assert tel.counter_value(name) == getattr(s, legacy), legacy

    def test_summary_keys_survive_verbatim(self):
        """The exact legacy summary lines downstream parsers read."""
        assert ServeStats().summary() == (
            "submitted=0 accepted=0 completed=0 shed=0 timeouts=0 "
            "rejected=0 errors=0 batches=0 padded_rows=0 quarantined=[-]"
        )
        assert StreamStats().summary() == (
            "submitted=0 accepted=0 completed=0 shed_streams=0 "
            "shed_frames=0 rejected=0 resets=0 errors=0 batches=0 "
            "padded_rows=0 opened=0 closed=0 evicted=0 cold_starts=0"
        )


# ------------------------------------------------------ admission gauges


class TestAdmissionQueueGauges:
    def _req(self, rid):
        return FlowRequest(rid, None, None, shape_key="a")

    def test_depth_observable_between_offer_and_pop(self):
        """The satellite fix: live depth is a gauge from the first
        offer, not something inferred from shed events after the fact."""
        tel = Telemetry()
        q = AdmissionQueue(8, telemetry=tel, name="serve")
        for i in range(3):
            q.offer(self._req(i))
        g = tel.registry.get("serve_queue_depth")
        assert g is not None and g.value == 3
        q.pop_batch(2)
        assert g.value == 1
        q.pop_batch(2)
        assert g.value == 0
        assert g.peak == 3

    def test_service_time_ema_gauge(self):
        tel = Telemetry()
        srv = FlowServer(_DummyModel(), {}, _cfg(), telemetry=tel)
        try:
            assert srv.submit(_img(1), _img(2)).result(60).ok
        finally:
            srv.drain()
        g = tel.registry.get("serve_service_time_ema_ms")
        assert g is not None and g.value > 0


# ------------------------------------ server spans / report back-compat


# Pre-telemetry report() keys, pinned verbatim (acceptance criterion).
SERVE_REPORT_KEYS = {
    "stats", "budget", "budget_drops", "budget_recoveries",
    "executables", "precision", "mesh",
}
STREAM_REPORT_KEYS = {
    "stats", "capacity", "occupancy", "peak_occupancy", "mean_occupancy",
    "evicted", "executables", "precision", "mesh",
}


class TestServerTelemetry:
    def test_span_correlation_through_a_real_two_request_batch(self):
        """Two requests paused into ONE batch: the journey of each
        request is reassemblable from the ring — its own queue-wait plus
        the batch-level assembly/stage/dispatch/drain spans, all tied by
        one batch id, with mesh+policy fingerprints on the dispatch."""
        tel = Telemetry()
        srv = FlowServer(_DummyModel(), {}, _cfg(), telemetry=tel)
        try:
            srv.pause()
            h1 = srv.submit(_img(1), _img(2))
            h2 = srv.submit(_img(3), _img(4))
            srv.resume()
            assert h1.result(60).ok and h2.result(60).ok
        finally:
            srv.drain()
        disp = tel.tracer.records("serve_dispatch")
        assert len(disp) == 1
        assert sorted(disp[0]["attrs"]["request_ids"]) == [0, 1]
        assert disp[0]["attrs"]["policy"] == "f32"
        assert "mesh" in disp[0]["attrs"]
        batch_id = disp[0]["attrs"]["batch_id"]
        journey = {
            r["name"] for r in tel.tracer.for_attr(request_id=0)
        }
        assert {
            "serve_queue_wait", "serve_dispatch", "serve_drain",
        } <= journey
        # Batch-level stages share the batch correlation id.
        for name in ("serve_batch_assembly", "serve_pad_stage",
                     "serve_drain"):
            recs = tel.tracer.records(name)
            assert recs and recs[-1]["attrs"]["batch_id"] == batch_id
        # Queue-wait recorded once per request.
        assert tel.registry.histogram("serve_queue_wait_ms").count == 2
        # One sanctioned pull for the one batch.
        assert tel.counter_value("serve_drain_pulls_total") == 1

    def test_serve_report_backcompat_plus_stages(self):
        tel = Telemetry()
        srv = FlowServer(_DummyModel(), {}, _cfg(), telemetry=tel)
        try:
            assert srv.submit(_img(1), _img(2)).result(60).ok
            report = srv.report()
        finally:
            srv.drain()
        assert SERVE_REPORT_KEYS <= set(report)
        assert "stages" in report
        assert report["stages"]["serve_dispatch"]["count"] == 1
        assert report["stages"]["serve_dispatch"]["p50_ms"] is not None
        # stats summary still parses with the legacy fields.
        assert report["stats"].startswith("submitted=1 accepted=1 ")

    def test_stream_report_backcompat_plus_stages(self):
        tel = Telemetry()
        eng = StreamEngine(
            _DummyVideoModel(), {},
            StreamConfig(capacity=2, frame_hw=(24, 32), iters=1,
                         batch_sizes=(1, 2), queue_capacity=8),
            telemetry=tel,
        )
        try:
            assert eng.submit("s0", _img(1), _img(2)).result(60).ok
            report = eng.report()
        finally:
            eng.drain()
        assert STREAM_REPORT_KEYS <= set(report)
        assert report["stages"]["stream_dispatch"]["count"] == 1
        # Slot admission landed as a correlated lifecycle event.
        (admit,) = tel.tracer.records("stream_slot_admitted")
        assert admit["attrs"]["stream_id"] == "s0"
        assert tel.counter_value("stream_drain_pulls_total") == 1

    def test_disabled_telemetry_serves_identically(self):
        tel = Telemetry(enabled=False)
        srv = FlowServer(_DummyModel(), {}, _cfg(), telemetry=tel)
        try:
            r = srv.submit(_img(1), _img(2)).result(60)
        finally:
            stats = srv.drain()
        assert r.ok and stats.completed == 1
        assert tel.tracer.records() == []
        assert srv.report()["stages"] == {}


# --------------------------------------------------------- export layer


class TestExport:
    def test_jsonl_sink_is_bounded(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        with JsonlSink(path, max_events=5) as sink:
            written = [sink.write({"i": i}) for i in range(9)]
        assert written == [True] * 5 + [False] * 4
        lines = [
            json.loads(ln) for ln in open(path, encoding="utf-8")
        ]
        # 5 events + the closing record carrying the drop count.
        assert len(lines) == 6
        assert lines[-1] == {"name": "jsonl_sink_closed", "dropped": 4}

    def test_periodic_snapshot_writes_reports(self, tmp_path):
        path = str(tmp_path / "snap.jsonl")
        tel = Telemetry()
        tel.inc("serve_requests_submitted_total", 3)
        with JsonlSink(path) as sink:
            snap = PeriodicSnapshot(tel, sink, interval_s=0.05).start()
            time.sleep(0.12)
            snap.stop()
        lines = [
            json.loads(ln) for ln in open(path, encoding="utf-8")
        ]
        assert len(lines) >= 2  # >=1 periodic + the final stop() one
        rep = lines[-1]["report"]
        assert rep["metrics"]["counters"][
            "serve_requests_submitted_total"
        ] == 3

    def test_telemetry_report_shape(self):
        tel = Telemetry()
        tel.inc("c_total")
        with tel.span("stage_x"):
            pass
        rep = telemetry_report(tel)
        assert rep["enabled"] is True
        assert rep["metrics"]["counters"]["c_total"] == 1
        assert "stage_x" in rep["stages"]
        assert rep["spans_recorded"] == 1
        assert json.loads(json.dumps(rep)) == rep


# ------------------------------------------- the platform invariant


@pytest.fixture(scope="module")
def tiny_model():
    cfg = small_model_config("raft", dataset="chairs")
    model = RAFT(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, 40, 48, 3))
    return model, variables


class TestTracingPreservesInvariants:
    def test_steady_state_sync_free_recompile_free_under_full_tracing(
        self, tiny_model, forbid_host_transfers, max_recompiles
    ):
        """The tentpole's hard constraint: with telemetry FULLY enabled
        (counters, spans, queue gauges all live), a warm steady-state
        serving window still performs ZERO implicit host pulls and ZERO
        compiles, and each batch still does exactly ONE sanctioned
        device_get — the observer adds bookkeeping, never a sync."""
        model, variables = tiny_model
        tel = Telemetry()
        cfg = _cfg(batch_sizes=(1,), iter_levels=(2, 1))
        srv = FlowServer(model, variables, cfg, telemetry=tel)
        try:
            srv.warmup((40, 48))
            warm = srv.submit(_img(30, (40, 48)), _img(31, (40, 48)))
            assert warm.result(120).ok
            pulls_before = tel.counter_value("serve_drain_pulls_total")
            with forbid_host_transfers() as stats, max_recompiles(0):
                handles = [
                    srv.submit(_img(40 + i, (40, 48)),
                               _img(50 + i, (40, 48)))
                    for i in range(3)
                ]
                rs = [h.result(120) for h in handles]
        finally:
            srv.drain()
        assert [r.status for r in rs] == [STATUS_OK] * 3
        assert stats.host_transfers == 0
        assert stats.sanctioned_gets == 3  # one per batch, as before
        # ...and tracing really was live through the guarded window:
        assert (
            tel.counter_value("serve_drain_pulls_total") - pulls_before
            == 3
        )
        assert tel.registry.histogram("serve_queue_wait_ms").count >= 3
        assert tel.tracer.records("serve_dispatch")


# -------------------------------------------- executable cache events


class TestExecutableCacheEvents:
    def test_compile_hit_evict_events_keyed_like_the_cache(self):
        from raft_ncup_tpu.inference.pipeline import ShapeCachedForward

        tel = Telemetry()
        fwd = ShapeCachedForward(
            _DummyModel(), {}, cache_size=1, telemetry=tel
        )
        calls = []
        fwd.custom(("k1",), lambda: calls.append("a") or (lambda: 1))
        fwd.custom(("k1",), lambda: calls.append("b") or (lambda: 2))
        fwd.custom(("k2",), lambda: calls.append("c") or (lambda: 3))
        assert calls == ["a", "c"]  # second k1 was a hit
        assert tel.counter_value(
            "inference_executable_compiles_total"
        ) == 2
        assert tel.counter_value("inference_executable_hits_total") == 1
        assert tel.counter_value(
            "inference_executable_evictions_total"
        ) == 1
        (compile1, compile2) = tel.tracer.records(
            "inference_executable_compile"
        )
        (evict,) = tel.tracer.records("inference_executable_evict")
        # Events carry the cache's own key (mesh fingerprint prefix
        # included) — "keyed like the cache".
        assert "k1" in compile1["attrs"]["key"]
        assert "k2" in compile2["attrs"]["key"]
        assert "k1" in evict["attrs"]["key"]
        assert fwd.stats == {"compiles": 2, "hits": 1, "evictions": 1}


# ------------------------------- guard + logger registry producers


class TestGuardAndLoggerMirrors:
    def test_guard_violation_lands_as_event(self):
        """GuardStats re-expressed over the registry: an intercepted
        implicit pull shows on the process-default hub's timeline."""
        from raft_ncup_tpu.analysis.guards import forbid_host_transfers
        from raft_ncup_tpu.observability import set_telemetry

        prev = set_telemetry(Telemetry())
        try:
            x = jnp.ones((2,))
            with forbid_host_transfers(raise_on_violation=False) as gs:
                float(x[0])  # the planted implicit pull
                jax.device_get(x)  # sanctioned
            from raft_ncup_tpu.observability import get_telemetry

            tel = get_telemetry()
            assert gs.host_transfers == 1
            assert tel.counter_value(
                "guard_host_transfer_violation_total"
            ) == 1
            (ev,) = tel.tracer.records("guard_host_transfer_violation")
            assert "jax.Array" in ev["attrs"]["desc"]
            assert tel.counter_value("guard_sanctioned_gets_total") >= 1
        finally:
            set_telemetry(prev)

    def test_logger_window_means_land_as_gauges(self, tmp_path):
        from raft_ncup_tpu.observability import set_telemetry
        from raft_ncup_tpu.training.logger import Logger

        prev = set_telemetry(Telemetry())
        try:
            log = Logger(str(tmp_path), sum_freq=2, use_tensorboard=False)
            log.push(0, {"loss": jnp.asarray(4.0)}, lr=1e-4)
            log.push(1, {"loss": jnp.asarray(2.0)}, lr=1e-4)
            log.close()
            from raft_ncup_tpu.observability import get_telemetry

            reg = get_telemetry().registry
            assert reg.get("train_loss").value == 3.0  # window mean
            assert reg.get("train_lr").value == pytest.approx(1e-4)
            assert reg.get("train_steps_per_sec").value > 0
        finally:
            set_telemetry(prev)


# ------------------------------------------------ health state machine


class TestHealthStateMachine:
    def test_lifecycle_path_and_codes(self):
        tel = Telemetry()
        h = HealthTracker("serve", telemetry=tel)
        assert h.state == STARTING
        assert h.warming() and h.state == WARMING
        assert h.ready("warmup done") and h.state == READY
        assert h.degrade("slo burning") and h.state == DEGRADED
        assert h.ready("slo recovered") and h.state == READY
        assert h.draining() and h.state == DRAINING
        assert h.halted("fatal") and h.state == HALTED
        snap = h.snapshot()
        assert snap["state"] == HALTED
        assert snap["code"] == STATE_CODES[HALTED] == 5
        assert snap["transitions"] == 6
        # Transitions published as gauge + correlated events.
        assert tel.registry.get("serve_health_state").value == 5
        recs = tel.tracer.records("serve_health_transition")
        assert [r["attrs"]["to_state"] for r in recs] == [
            WARMING, READY, DEGRADED, READY, DRAINING, HALTED,
        ]

    def test_illegal_transitions_are_counted_noops_never_raise(self):
        tel = Telemetry()
        h = HealthTracker("x", telemetry=tel)
        assert not h.degrade("no")  # STARTING -> DEGRADED illegal
        assert h.state == STARTING
        h.draining()
        assert not h.ready("no")  # DRAINING -> READY illegal
        h.halted("end")
        assert not h.draining()  # HALTED is terminal
        assert h.snapshot()["invalid_transitions"] == 3
        assert tel.counter_value("x_health_invalid_transition_total") == 3

    def test_same_state_is_silent_noop(self):
        h = HealthTracker("x")
        h.draining()
        assert not h.draining()  # drain() is idempotent upstream
        assert h.snapshot()["transitions"] == 1

    def test_unknown_state_raises(self):
        with pytest.raises(ValueError, match="unknown health state"):
            HealthTracker("x").to("broken")

    def test_state_tracks_even_when_hub_disabled(self):
        """Health is product logic (budget gate, healthz): the STATE
        machine runs with telemetry off; only the exports are muted."""
        tel = Telemetry(enabled=False)
        h = tel.health("serve")
        h.warming(), h.ready()
        assert h.state == READY
        assert tel.tracer.records() == []
        assert tel.registry.names() == []

    def test_hub_accessor_get_or_create_and_fresh(self):
        tel = Telemetry()
        a = tel.health("serve")
        assert tel.health("serve") is a
        a.draining()
        b = tel.health("serve", fresh=True)  # re-entrant driver run
        assert b is not a and b.state == STARTING
        assert tel.health_snapshot()["serve"]["state"] == STARTING

    def test_overall_state_is_worst(self):
        assert overall_state({}) == READY
        assert overall_state({
            "serve": {"state": READY}, "stream": {"state": DEGRADED},
        }) == DEGRADED
        assert overall_state({
            "serve": {"state": STARTING}, "train": {"state": HALTED},
        }) == HALTED


# ------------------------------------------------------ slo burn engine


def _clocked(start=0.0):
    t = {"now": float(start)}

    def clk():
        return t["now"]

    return t, clk


class TestSloSpec:
    def test_validation(self):
        with pytest.raises(ValueError, match="objective"):
            SloSpec("a", "serve", "ratio", objective=1.0,
                    bad="b", total="t")
        with pytest.raises(ValueError, match="sli"):
            SloSpec("a", "serve", "nope", objective=0.9)
        with pytest.raises(ValueError, match="metric fields"):
            SloSpec("a", "serve", "ratio", objective=0.9, bad="b")
        with pytest.raises(ValueError, match="fast_window_s"):
            SloSpec("a", "serve", "gauge", objective=0.9, gauge="g",
                    max_value=1, fast_window_s=10, slow_window_s=5)

    def test_scaled_shrinks_windows_only(self):
        s = serve_slos(window_scale=0.01)[0]
        assert s.fast_window_s == pytest.approx(3.0)
        assert s.slow_window_s == pytest.approx(36.0)
        assert s.objective == serve_slos()[0].objective


class TestSloEngine:
    def _engine(self, spec, tel, clk):
        return SloEngine([spec], tel, clock=clk)

    def test_ratio_burn_math_is_exact(self):
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        spec = SloSpec("shed", "serve", "ratio", objective=0.9,
                       bad="bad_total", total="all_total",
                       fast_window_s=10, slow_window_s=60,
                       page_burn=2.0, min_events=1)
        eng = self._engine(spec, tel, clk)
        eng.evaluate()  # baseline
        tel.inc("all_total", 10)
        tel.inc("bad_total", 5)
        t["now"] = 1.0
        v = eng.evaluate()["shed"]
        # bad fraction 0.5 over budget 0.1 => burn 5.0, both windows.
        assert v.burn_fast == pytest.approx(5.0)
        assert v.burn_slow == pytest.approx(5.0)
        assert v.page and eng.paging("serve") and eng.paging()

    def test_min_events_gates_paging(self):
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        spec = SloSpec("shed", "serve", "ratio", objective=0.9,
                       bad="bad_total", total="all_total",
                       fast_window_s=10, slow_window_s=60,
                       page_burn=2.0, min_events=8)
        eng = self._engine(spec, tel, clk)
        eng.evaluate()
        tel.inc("all_total", 2)
        tel.inc("bad_total", 2)  # 100% bad, but only 2 events
        t["now"] = 1.0
        assert not eng.evaluate()["shed"].page

    def test_page_requires_both_windows(self):
        """The multi-window discipline: an old burst still inside the
        slow window but outside the fast one must NOT page."""
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        spec = SloSpec("shed", "serve", "ratio", objective=0.9,
                       bad="bad_total", total="all_total",
                       fast_window_s=3, slow_window_s=60,
                       page_burn=2.0, min_events=1)
        eng = self._engine(spec, tel, clk)
        eng.evaluate()
        tel.inc("all_total", 10)
        tel.inc("bad_total", 10)
        t["now"] = 1.0
        assert eng.evaluate()["shed"].page  # fresh burst: pages
        t["now"] = 30.0  # burst now outside fast window, inside slow
        v = eng.evaluate()["shed"]
        assert v.burn_fast == 0.0 and v.burn_slow > 2.0
        assert not v.page

    def test_latency_sli_counts_over_threshold_fraction(self):
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        spec = SloSpec("p99", "serve", "latency", objective=0.5,
                       histogram="e2e_ms", threshold_ms=100.0,
                       fast_window_s=10, slow_window_s=60,
                       page_burn=1.5, min_events=1)
        eng = self._engine(spec, tel, clk)
        eng.evaluate()
        for _ in range(10):
            tel.hist_observe("e2e_ms", 50.0)  # <= 100: good
        for _ in range(10):
            tel.hist_observe("e2e_ms", 500.0)  # > 100: bad
        t["now"] = 1.0
        v = eng.evaluate()["p99"]
        assert v.bad_fraction_fast == pytest.approx(0.5)
        assert v.burn_fast == pytest.approx(1.0)  # 0.5 / budget 0.5
        assert not v.page  # burn 1.0 < page_burn 1.5

    def test_gauge_sli_fraction_of_bad_samples(self):
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        spec = SloSpec("occ", "stream", "gauge", objective=0.5,
                       gauge="occupancy", max_value=3.0,
                       fast_window_s=10, slow_window_s=60,
                       page_burn=1.9, min_events=2)
        eng = self._engine(spec, tel, clk)
        for i, val in enumerate([4, 4, 4, 4]):
            tel.gauge_set("occupancy", val)
            t["now"] = float(i)
            eng.evaluate()
        v = eng.verdicts()["occ"]
        assert v.bad_fraction_fast == 1.0
        assert v.burn_fast == pytest.approx(2.0)
        assert v.page

    def test_page_edge_flips_health_and_clear_restores(self):
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        tel.health("serve").ready("test")
        spec = SloSpec("shed", "serve", "ratio", objective=0.9,
                       bad="bad_total", total="all_total",
                       fast_window_s=3, slow_window_s=30,
                       page_burn=2.0, min_events=1)
        eng = self._engine(spec, tel, clk)
        tel.slo = eng
        eng.evaluate()
        tel.inc("all_total", 10)
        tel.inc("bad_total", 10)
        t["now"] = 1.0
        eng.evaluate()
        assert tel.health("serve").state == DEGRADED
        assert tel.counter_value("slo_page_total") == 1
        assert tel.slo_paging("serve") and not tel.slo_paging("stream")
        # Burn gauges published for the scrape surface.
        assert tel.registry.get("slo_shed_burn_fast").value > 2.0
        t["now"] = 60.0  # everything aged out of both windows
        eng.evaluate()
        assert tel.health("serve").state == READY
        assert tel.counter_value("slo_clear_total") == 1
        assert not tel.slo_paging("serve")
        snap = eng.snapshot()
        assert snap["paging"] == [] and snap["pages_total"] == 1
        assert json.loads(json.dumps(snap)) == snap

    def test_no_engine_means_no_paging(self):
        assert not Telemetry().slo_paging("serve")


# ------------------------------------------------------ flight recorder


class TestFlightRecorder:
    def _hub(self, tmp_path, **kw):
        tel = Telemetry()
        tel.flight = FlightRecorder(
            str(tmp_path / "flight"), min_interval_s=0.0, **kw
        )
        return tel

    def test_dump_contains_ring_report_and_fingerprints(self, tmp_path):
        tel = self._hub(tmp_path)
        tel.health("serve").ready("test")
        with tel.span("serve_dispatch", batch_id=3, request_ids=[7, 8],
                      mesh="mesh(d1s2)", policy="bf16_infer"):
            pass
        tel.event("serve_request_quarantined", request_id=7)
        path = tel.flight_dump("poison_quarantine", request_id=7,
                               batch_id=3, detail="nan in image1")
        assert path and path.endswith(".json")
        assert not [p for p in os.listdir(tmp_path / "flight")
                    if p.endswith(".tmp")]  # atomic rename, no residue
        dump = load_dump(path)
        assert dump["trigger"] == "poison_quarantine"
        assert dump["context"]["request_id"] == 7
        assert dump["fingerprints"] == {
            "mesh": "mesh(d1s2)", "policy": "bf16_infer",
        }
        assert dump["report"]["health"]["serve"]["state"] == READY
        journey = match_records(dump["spans"], request_id=7)
        assert {r["name"] for r in journey} == {
            "serve_dispatch", "serve_request_quarantined",
        }
        assert tel.counter_value("flight_dump_total") == 1

    def test_rate_limit_suppresses_and_counts(self, tmp_path):
        tel = Telemetry()
        tel.flight = FlightRecorder(
            str(tmp_path / "flight"), min_interval_s=100.0
        )
        assert tel.flight_dump("poison_quarantine") is not None
        assert tel.flight_dump("poison_quarantine") is None  # limited
        assert tel.flight_dump("slo_page") is not None  # per-trigger
        assert tel.flight.suppressed == 1
        assert tel.counter_value("flight_dump_suppressed_total") == 1

    def test_failed_write_does_not_rate_limit_the_retry(self, tmp_path):
        """Review regression: the limiter throttles SUCCESSES — a
        transient write failure must leave the window open, or one I/O
        hiccup at the first fault silences the whole interval."""
        blocker = tmp_path / "blocked"
        blocker.write_text("a file where the dump dir should be")
        tel = Telemetry()
        tel.flight = FlightRecorder(str(blocker), min_interval_s=100.0)
        assert tel.flight_dump("guard_violation") is None  # write fails
        assert tel.flight.failed == 1
        tel.flight.directory = str(tmp_path / "flight")  # I/O recovers
        # Immediately retryable: NOT suppressed by the failed attempt.
        assert tel.flight_dump("guard_violation") is not None
        assert tel.flight.suppressed == 0
        # A SUCCESS does arm the limiter.
        assert tel.flight_dump("guard_violation") is None
        assert tel.flight.suppressed == 1

    def test_dump_cap_deletes_oldest(self, tmp_path):
        tel = self._hub(tmp_path, max_dumps=2)
        for i in range(4):
            assert tel.flight_dump("guard_violation", i=i)
        names = sorted(os.listdir(tmp_path / "flight"))
        assert len(names) == 2
        kept = [load_dump(str(tmp_path / "flight" / n))["context"]["i"]
                for n in names]
        assert kept == [2, 3]

    def test_disabled_hub_and_absent_recorder_are_noops(self, tmp_path):
        assert Telemetry().flight_dump("x") is None
        tel = self._hub(tmp_path)
        tel.enabled = False
        assert tel.flight_dump("x") is None
        assert not (tmp_path / "flight").exists()

    def test_load_dump_rejects_foreign_json(self, tmp_path):
        p = tmp_path / "not_a_dump.json"
        p.write_text('{"hello": 1}')
        with pytest.raises(ValueError, match="not a flight-recorder"):
            load_dump(str(p))

    def test_match_records_parity_with_for_attr(self):
        """The offline matcher and the live tracer must agree — the
        postmortem tool reads dumps with match_records."""
        tel = Telemetry()
        tel.event("a", request_ids=[1, 2], batch_id=9)
        tel.event("b", request_id=1)
        tel.event("c", request_id=3)
        recs = tel.tracer.records()
        assert match_records(recs, request_id=1) == tel.tracer.for_attr(
            request_id=1
        )
        assert match_records(recs, batch_id=9) == tel.tracer.for_attr(
            batch_id=9
        )


# --------------------------------------- periodic snapshot lifecycle


class TestPeriodicSnapshotLifecycle:
    def test_stop_before_start_is_noop(self, tmp_path):
        """The satellite fix: stop() on a never-started monitor must not
        write a phantom 'final' snapshot."""
        path = str(tmp_path / "snap.jsonl")
        with JsonlSink(path) as sink:
            snap = PeriodicSnapshot(Telemetry(), sink, interval_s=5.0)
            snap.stop()  # never started
            assert sink.write({"probe": 1})  # sink untouched and open
        lines = [json.loads(l) for l in open(path, encoding="utf-8")]
        assert lines == [{"probe": 1}]

    def test_teardown_orders_final_snapshot_before_sink_close(
        self, tmp_path
    ):
        """The serve.py teardown contract: the final stop() snapshot —
        the one describing the drained end state — lands in the sink
        BEFORE it closes (nested contexts, inner exits first)."""
        path = str(tmp_path / "snap.jsonl")
        tel = Telemetry()
        with JsonlSink(path) as sink:
            with PeriodicSnapshot(tel, sink, interval_s=60.0):
                tel.inc("late_fact_total", 7)  # only the final tick sees it
        lines = [json.loads(l) for l in open(path, encoding="utf-8")]
        snaps = [l for l in lines if l.get("name") == "telemetry_snapshot"]
        assert len(snaps) >= 2  # immediate start tick + final stop tick
        assert snaps[-1]["report"]["metrics"]["counters"][
            "late_fact_total"
        ] == 7  # the final snapshot was WRITTEN, not dropped on a closed sink

    def test_healthz_written_immediately_and_atomically(self, tmp_path):
        path = str(tmp_path / "healthz.json")
        tel = Telemetry()
        tel.health("serve").ready("test")
        snap = PeriodicSnapshot(tel, None, interval_s=60.0,
                                healthz_path=path)
        snap.start()
        hz = json.load(open(path, encoding="utf-8"))
        assert hz["overall"] == READY and not hz["draining"]
        assert hz["exit_contract"] == {"draining": 75, "halted": 76}
        tel.health("serve").draining()
        snap.stop()
        hz = json.load(open(path, encoding="utf-8"))
        assert hz["overall"] == DRAINING and hz["draining"]
        assert not os.path.exists(path + ".tmp")

    def test_snapshot_tick_evaluates_attached_slo(self, tmp_path):
        tel = Telemetry()
        tel.slo = SloEngine(serve_slos(), tel)
        with PeriodicSnapshot(tel, None, interval_s=60.0):
            pass
        assert set(tel.slo.snapshot()["verdicts"]) == {
            s.name for s in serve_slos()
        }

    def test_write_healthz_direct(self, tmp_path):
        path = str(tmp_path / "hz.json")
        tel = Telemetry()
        write_healthz(path, tel)
        hz = json.load(open(path, encoding="utf-8"))
        assert hz["health"] == {} and hz["slo"] is None

    def test_healthz_replica_identity_schema(self, tmp_path):
        """The fleet-facing healthz schema (docs/FLEET.md): pid +
        process start time always present; the producer-deposited
        identity (mesh fingerprint, warmed executable set) merged
        verbatim; the cadence published WITH its staleness contract
        (stale_after_s = 2x interval) so a consumer never has to guess
        how old is dead."""
        import os as _os

        path = str(tmp_path / "hz.json")
        tel = Telemetry()
        tel.identity.update({
            "replica": 3,
            "mesh": "mesh(data=1,spatial=1)",
            "warmed": [[48, 64, 1, 2], [48, 64, 2, 2]],
        })
        write_healthz(path, tel, interval_s=0.25)
        hz = json.load(open(path, encoding="utf-8"))
        # Replica identity: who is answering this file.
        assert hz["pid"] == _os.getpid()
        assert hz["start_time_unix_s"] <= hz["time_unix_s"]
        assert hz["replica"] == 3
        assert hz["mesh"] == "mesh(data=1,spatial=1)"
        assert hz["warmed"] == [[48, 64, 1, 2], [48, 64, 2, 2]]
        # The staleness contract, pinned: the writer promises the
        # cadence, the consumer must treat 2x it as dead.
        assert hz["interval_s"] == 0.25
        assert hz["stale_after_s"] == 0.5
        from raft_ncup_tpu.fleet import healthz_fresh

        assert healthz_fresh(hz, hz["stale_after_s"])
        assert not healthz_fresh(
            hz, hz["stale_after_s"],
            now_unix=hz["time_unix_s"] + 2.01 * hz["interval_s"],
        )
        # Without an interval the identity fields still land, and the
        # cadence fields are absent rather than invented.
        write_healthz(path, tel)
        hz = json.load(open(path, encoding="utf-8"))
        assert "interval_s" not in hz and "stale_after_s" not in hz
        assert hz["pid"] == _os.getpid()


# -------------------------------------------- prometheus compliance


_SAMPLE_RE = None


class TestPrometheusCompliance:
    """A mini-parser pinning the exposition format a real scraper
    ingests unmodified: name charset, TYPE lines for every family,
    histogram bucket/sum/count triplet with cumulative +Inf."""

    def _parse(self, text):
        import re

        name_re = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*\Z")
        sample_re = re.compile(
            r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
            r'(\{le="[^"]+"\})? '
            r"(-?[0-9.eE+]+|\+Inf|NaN)$"
        )
        types, samples = {}, []
        for line in text.splitlines():
            if not line:
                continue
            if line.startswith("# TYPE "):
                _, _, name, kind = line.split(" ", 3)
                assert name_re.match(name), line
                assert kind in ("counter", "gauge", "histogram"), line
                assert name not in types, f"duplicate TYPE: {line}"
                types[name] = kind
            elif line.startswith("# HELP "):
                assert "\n" not in line
            else:
                m = sample_re.match(line)
                assert m, f"malformed sample line: {line!r}"
                samples.append((m.group(1), m.group(2), m.group(3)))
        return types, samples

    def _family(self, name, types):
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[: -len(suffix)] if name.endswith(suffix) else None
            if base and types.get(base) == "histogram":
                return base
        return name

    def test_every_sample_has_a_typed_family(self):
        reg = MetricsRegistry()
        reg.counter("serve_requests_shed_total", help="shed requests").inc(2)
        reg.gauge("serve_queue_depth").set(5)
        reg.histogram("serve_drain_ms").observe_ms(3.0)
        reg.histogram("serve_drain_ms").observe_ms(7000.0)
        types, samples = self._parse(reg.prometheus_text())
        assert samples, "no samples emitted"
        for name, _, _ in samples:
            fam = self._family(name, types)
            assert fam in types, f"untyped family for sample {name}"
        # The gauge's peak companion is its own typed gauge family.
        assert types["serve_queue_depth_peak"] == "gauge"

    def test_histogram_triplet_cumulative_plus_inf(self):
        reg = MetricsRegistry()
        h = reg.histogram("x_ms")
        for ms in (0.5, 3.0, 3.0, 250.0, 99999.0):
            h.observe_ms(ms)
        types, samples = self._parse(reg.prometheus_text())
        buckets = [
            (label, float(v)) for name, label, v in samples
            if name == "x_ms_bucket"
        ]
        counts = [v for _, v in buckets]
        assert counts == sorted(counts), "buckets must be cumulative"
        assert buckets[-1][0] == '{le="+Inf"}'
        count = next(
            float(v) for name, _, v in samples if name == "x_ms_count"
        )
        assert buckets[-1][1] == count == 5
        assert any(name == "x_ms_sum" for name, _, _ in samples)

    def test_names_sanitized_to_exposition_charset(self):
        reg = MetricsRegistry()
        reg.counter("serve queue.depth-total").inc()
        reg.counter("0starts_with_digit").inc()
        types, samples = self._parse(reg.prometheus_text())
        names = {n for n, _, _ in samples}
        assert "serve_queue_depth_total" in names
        assert "_0starts_with_digit" in names

    def test_help_text_escaped_to_one_line(self):
        reg = MetricsRegistry()
        reg.counter("c_total", help="line one\nline two \\ backslash")
        text = reg.prometheus_text()
        self._parse(text)  # no malformed lines
        assert r"line one\nline two \\ backslash" in text


# ------------------------------------------- the closed loop, end to end


class TestClosedLoop:
    def test_chaos_burst_poison_drives_degrade_then_recovery(
        self, tmp_path
    ):
        """The tentpole acceptance trajectory, deterministic end to end:
        a burst past queue capacity (sheds) plus a poison request drive
        the declared shed-rate SLO into burn -> the page edge flips
        health READY -> DEGRADED and arms the budget controller's second
        degrade input -> the controller walks down the level set (at
        least one drop attributable to the SLO alone, occupancy below
        high water) -> the burst ages out of both burn windows -> the
        clear edge restores READY -> sustained calm recovers the budget
        level by level. Exact state and level trajectories asserted;
        the slo_page and poison_quarantine faults each left a flight
        dump."""
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        tel.flight = FlightRecorder(
            str(tmp_path / "flight"), min_interval_s=0.0
        )
        tel.slo = SloEngine(serve_slos(window_scale=0.01), tel, clock=clk)
        cfg = _cfg(
            queue_capacity=8, batch_sizes=(1, 2),
            iter_levels=(8, 4, 2), high_water=1.0, low_water=0.25,
            recover_patience=2,
        )
        srv = FlowServer(_DummyModel(), {}, cfg, telemetry=tel)
        try:
            srv.warmup((24, 32))
            assert srv.health.state == READY
            tel.slo.evaluate()  # baseline sample at t=0

            # ---- burst + poison: 12 submits against capacity 8 ------
            srv.pause()
            poison = _img(5)
            poison[3, 3, 0] = np.nan
            handles = []
            for i in range(12):
                img = poison if i == 7 else _img(10 + i)
                handles.append(srv.submit(img, _img(30 + i)))
            assert srv.stats.shed == 4  # 12 offered, capacity 8
            t["now"] = 1.0
            verdicts = tel.slo.evaluate()
            # shed fraction 4/12 over budget 0.01 -> burn ~33x: page.
            assert verdicts["serve_shed_rate"].page
            assert srv.health.state == DEGRADED

            # ---- degraded dispatch: the SLO drives the knob ---------
            srv.resume()
            responses = [h.result(60) for h in handles]
        finally:
            srv.drain()
        ok = [r for r in responses if r.status == STATUS_OK]
        rejected = [r for r in responses if r.status == "rejected"]
        assert len(ok) == 7 and len(rejected) == 1  # poison quarantined
        # 4 batches of 2: level 0 -> 1 (occupancy at full queue, paging)
        # -> 2 (paging ALONE: occupancy already back under high water)
        # -> floor. Per-batch budgets land in the responses.
        assert sorted(r.iters for r in ok) == [2, 2, 2, 2, 2, 4, 4]
        assert srv.budget.level == 2
        assert srv.budget.drops == 2
        assert srv.budget.slo_drops >= 1  # telemetry drove the knob
        assert srv.report()["budget_slo_drops"] == srv.budget.slo_drops

        # ---- recovery: burn windows drain, then earned calm ---------
        t["now"] = 60.0  # past the scaled slow window
        tel.slo.evaluate()
        assert not tel.slo_paging("serve")
        assert srv.health.state == DRAINING  # drain() above ran already

        # Re-run the recovery phase on a fresh server sharing the hub's
        # (now clean) SLO verdicts: four calm single-request decisions
        # recover 2 levels with patience 2.
        srv2 = FlowServer(_DummyModel(), {}, cfg, telemetry=tel)
        try:
            srv2.warmup((24, 32))
            srv2.budget._level = 2  # resume from the degraded level
            iters_seen = []
            for i in range(4):
                r = srv2.submit(_img(70 + i), _img(80 + i)).result(60)
                assert r.ok
                iters_seen.append(r.iters)
        finally:
            srv2.drain()
        assert iters_seen == [2, 4, 4, 8]
        assert srv2.budget.recoveries == 2 and srv2.budget.level == 0

        # ---- health trajectory + flight evidence --------------------
        transitions = [
            (h["from"], h["to"]) for h in srv.health.history()
        ]
        assert transitions == [
            (STARTING, WARMING),
            (WARMING, READY),
            (READY, DEGRADED),
            (DEGRADED, DRAINING),
        ]
        dumps = sorted(os.listdir(tmp_path / "flight"))
        assert sum("slo_page" in d for d in dumps) == 1
        assert sum("poison_quarantine" in d for d in dumps) == 1
        # The poison dump reassembles the faulting request's journey.
        poison_dump = next(
            d for d in dumps if "poison_quarantine" in d
        )
        dump = load_dump(str(tmp_path / "flight" / poison_dump))
        assert dump["context"]["request_id"] == 7
        journey = match_records(dump["spans"], request_id=7)
        assert "serve_queue_wait" in {r["name"] for r in journey}


# ------------------- guarded window with the full consumer half armed


class TestConsumersPreserveInvariants:
    def test_guarded_window_with_health_slo_flight_enabled(
        self, tiny_model, forbid_host_transfers, max_recompiles,
        tmp_path,
    ):
        """The tentpole's standing constraint extended to the consumer
        half: with health tracking, the SLO engine (evaluated INSIDE the
        guarded window), and the flight recorder all armed, a warm
        steady-state serving window still performs ZERO implicit host
        pulls and ZERO compiles, with exactly one sanctioned get per
        batch — the closed loop observes and decides without ever
        touching the device."""
        model, variables = tiny_model
        tel = Telemetry()
        tel.flight = FlightRecorder(str(tmp_path / "flight"))
        tel.slo = SloEngine(serve_slos(), tel)
        cfg = _cfg(batch_sizes=(1,), iter_levels=(2, 1))
        srv = FlowServer(model, variables, cfg, telemetry=tel)
        try:
            srv.warmup((40, 48))
            warm = srv.submit(_img(30, (40, 48)), _img(31, (40, 48)))
            assert warm.result(120).ok
            tel.slo.evaluate()  # baseline
            with forbid_host_transfers() as stats, max_recompiles(0):
                handles = [
                    srv.submit(_img(40 + i, (40, 48)),
                               _img(50 + i, (40, 48)))
                    for i in range(3)
                ]
                rs = [h.result(120) for h in handles]
                verdicts = tel.slo.evaluate()  # burn math inside guards
        finally:
            srv.drain()
        assert [r.status for r in rs] == [STATUS_OK] * 3
        assert stats.host_transfers == 0
        assert stats.sanctioned_gets == 3  # one per batch, unchanged
        assert srv.health.state == DRAINING  # via drain(); READY inside
        assert not any(v.page for v in verdicts.values())
        # No fault triggered: the recorder stayed quiet.
        assert tel.flight.dumps == 0
        # e2e latency histogram fed the latency SLI without a ring record.
        assert tel.registry.get("serve_e2e_ms").count >= 3
        rep = telemetry_report(tel)
        assert rep["health"]["serve"]["state"] == DRAINING
        assert rep["slo"]["verdicts"]


class TestSloEngineReviewRegressions:
    def test_ring_overflow_thins_resolution_not_the_window(
        self, monkeypatch
    ):
        """Review regression: at a sub-second cadence (fleet replicas
        tick every 0.25 s) a blind sample cap would evict the slow
        window's delta base and silently compute burn_slow over
        cap x cadence seconds instead of the DECLARED slow window. On
        overflow the ring must halve resolution, keeping its oldest
        in-window sample."""
        import raft_ncup_tpu.observability.slo as slo_mod

        monkeypatch.setattr(slo_mod, "_RING_CAP", 64)
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        spec = SloSpec("shed", "serve", "ratio", objective=0.9,
                       bad="bad_total", total="all_total",
                       fast_window_s=10, slow_window_s=100,
                       page_burn=2.0, min_events=1)
        eng = SloEngine([spec], tel, clock=clk)
        # A burst of bad events early, then a long clean steady state:
        # only a full-width slow window still sees the burst's delta.
        tel.inc("all_total", 10)
        tel.inc("bad_total", 10)
        for i in range(400):  # 200 s at 0.5 s cadence >> cap 64
            t["now"] = i * 0.5
            tel.inc("all_total", 1)  # clean traffic
            eng.evaluate()
        ring = eng._samples["shed"]
        # Memory stays bounded near the cap...
        assert len(ring) <= 2 * 64
        # ...and the base still spans the DECLARED window: the oldest
        # kept sample is ~100 s old, not 64 x 0.5 = 32 s.
        now = t["now"]
        assert now - ring[0][0] >= spec.slow_window_s * 0.8
        # burn_slow therefore reflects the full window's clean delta,
        # not a truncated horizon.
        v = eng.verdicts()["shed"]
        assert v.burn_slow < 2.0 and not v.page

    def test_gauge_occupancy_slo_can_actually_page(self):
        """Review regression: a gauge SLI saturates at bad_fraction 1.0,
        so its max burn is 1/(1-objective) — the declared occupancy SLO
        must keep that above page_burn or it can NEVER page (the 0.9
        objective capped burn at 10 < 14.4, silently)."""
        spec = next(
            s for s in stream_slos(capacity=4)
            if s.name == "stream_slot_occupancy"
        )
        assert 1.0 / spec.budget >= spec.page_burn
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        eng = SloEngine(
            [spec.scaled(0.001)], tel, clock=clk
        )  # fast 0.3s / slow 3.6s windows
        tel.gauge_set("stream_slot_occupancy", 4)  # pinned full table
        for i in range(80):
            t["now"] = i * 0.05
            eng.evaluate()
        assert eng.verdicts()["stream_slot_occupancy"].page

    def test_page_during_warming_degrades_once_ready(self):
        """Review regression: a page edge while the tracker is still
        STARTING/WARMING is an illegal degrade edge (no-op); the ONGOING
        page must still flip health the next evaluation after the
        subsystem becomes READY — edges alone would leave it 'ready'
        for the whole page."""
        t, clk = _clocked()
        tel = Telemetry(clock=clk)
        spec = SloSpec("shed", "serve", "ratio", objective=0.9,
                       bad="bad_total", total="all_total",
                       fast_window_s=30, slow_window_s=300,
                       page_burn=2.0, min_events=1)
        eng = SloEngine([spec], tel, clock=clk)
        tracker = tel.health("serve")
        tracker.warming()  # page will fire during warmup
        eng.evaluate()
        tel.inc("all_total", 10)
        tel.inc("bad_total", 10)
        t["now"] = 1.0
        eng.evaluate()
        assert eng.paging("serve")
        assert tracker.state == WARMING  # degrade edge was illegal here
        tracker.ready("warmup done")
        t["now"] = 2.0
        eng.evaluate()  # page still ongoing: degrade re-asserted
        assert tracker.state == DEGRADED
        # And a fresh tracker (re-entrant driver) degrades too.
        fresh = tel.health("serve", fresh=True)
        fresh.ready("second server")
        t["now"] = 3.0
        eng.evaluate()
        assert fresh.state == DEGRADED


# ---------------------------------------------------------------- traces


class TestTraceContext:
    """Cross-process trace context (observability/spans.py): the
    serializable (trace_id, parent span_id, clock offset) that rides the
    fleet wire header as an OPTIONAL field."""

    def test_wire_round_trip(self):
        from raft_ncup_tpu.observability import TraceContext

        ctx = TraceContext("abcd1234", "router-7", 0.125, 42.5)
        wire = ctx.to_wire()
        assert json.loads(json.dumps(wire)) == wire  # JSON-able
        back = TraceContext.from_wire(wire)
        assert back == ctx

    def test_from_wire_tolerates_absent_and_garbage(self):
        """Old peers send no context; corrupt headers send nonsense —
        both parse to None, never an exception (the wire-compat
        contract JGL010 pins statically)."""
        from raft_ncup_tpu.observability import TraceContext

        assert TraceContext.from_wire(None) is None
        assert TraceContext.from_wire("not-a-dict") is None
        assert TraceContext.from_wire({}) is None
        assert TraceContext.from_wire({"trace_id": 7}) is None
        assert TraceContext.from_wire(
            {"trace_id": "x", "sent_s": "garbage"}
        ) is None
        # Minimal valid: just a trace id.
        ctx = TraceContext.from_wire({"trace_id": "x"})
        assert ctx is not None and ctx.trace_id == "x"
        assert ctx.clock_offset_s == 0.0 and ctx.sent_s is None

    def test_child_reparents_same_trace(self):
        from raft_ncup_tpu.observability import TraceContext

        ctx = TraceContext("t1", "root", 0.5, 1.0)
        kid = ctx.child("replica-3", sent_s=2.0)
        assert kid.trace_id == "t1"
        assert kid.span_id == "replica-3"
        assert kid.clock_offset_s == 0.5
        assert kid.sent_s == 2.0

    def test_trace_ids_are_unique(self):
        from raft_ncup_tpu.observability import new_trace_id

        ids = {new_trace_id() for _ in range(64)}
        assert len(ids) == 64
        assert all(len(i) == 16 for i in ids)


class TestRecordTimestamps:
    """Every ring record stamps ``t_s`` (its start on the tracer's
    monotonic clock) — the absolute anchor aggregate.py orders
    cross-process timelines by."""

    def test_span_event_and_observe_carry_t_s(self):
        t = {"now": 100.0}
        tracer = SpanTracer(MetricsRegistry(), clock=lambda: t["now"])
        with tracer.span("stage_a"):
            t["now"] = 100.25
        tracer.event("thing_happened")
        t["now"] = 101.0
        tracer.observe_ms("stage_b", 500.0)  # ended now, started -0.5s
        recs = {r["name"]: r for r in tracer.records()}
        assert recs["stage_a"]["t_s"] == 100.0
        assert recs["stage_a"]["duration_ms"] == 250.0
        assert recs["thing_happened"]["t_s"] == 100.25
        assert recs["stage_b"]["t_s"] == pytest.approx(100.5)


class TestAggregate:
    """observability/aggregate.py: tolerant readers, the stitched fleet
    trace tree with clock-offset translation, per-hop attribution, and
    the merged registry view that marks dead replicas as gaps."""

    @staticmethod
    def _dump(path, spans, context=None):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({
                "flight_recorder_version": 1,
                "trigger": "test",
                "time_unix_s": 0.0,
                "context": context or {},
                "fingerprints": {},
                "report": None,
                "spans": spans,
            }, fh)

    def test_read_jsonl_tolerant_skips_truncated_tail(self, tmp_path):
        """A replica killed mid-write leaves a partial last line: the
        reader skips and COUNTS it instead of raising (the satellite
        fix — a postmortem must survive the evidence of the fault)."""
        from raft_ncup_tpu.observability import read_jsonl_tolerant

        p = tmp_path / "replica_0_telemetry.jsonl"
        with open(p, "w") as fh:
            fh.write(json.dumps({"name": "telemetry_snapshot",
                                 "report": {"metrics": {}}}) + "\n")
            fh.write('{"name": "telemetry_snapshot", "repo')  # truncated
        records, skipped = read_jsonl_tolerant(str(p))
        assert len(records) == 1
        assert skipped == 1
        # Missing file: empty, not an exception.
        assert read_jsonl_tolerant(str(tmp_path / "absent.jsonl")) == ([], 0)

    def _fleet_tree(self, tmp_path, offset=5.0):
        """A synthetic two-process export: the router's ring (root span
        + dispatch event, offsets in the drain dump context) and replica
        1's ring (wire hop + queue wait + dispatch + drain), with the
        replica's clock ``offset`` seconds AHEAD of the router's."""
        tid = "aaaa000011112222"
        router = [
            {"name": "fleet_dispatch", "event": True, "t_s": 10.001,
             "attrs": {"request_id": 7, "replica": 1, "trace_id": tid}},
            {"name": "fleet_request", "duration_ms": 250.0, "t_s": 10.0,
             "attrs": {"request_id": 7, "replica": 1, "trace_id": tid}},
        ]
        replica = [
            {"name": "fleet_wire_hop", "duration_ms": 2.0,
             "t_s": 10.003 + offset,
             "attrs": {"request_id": 7, "trace_id": tid,
                       "parent_span_id": "router-7"}},
            {"name": "serve_queue_wait", "duration_ms": 40.0,
             "t_s": 10.003 + offset,
             "attrs": {"request_id": 7, "batch_id": 0,
                       "trace_id": tid}},
            {"name": "serve_dispatch", "duration_ms": 5.0,
             "t_s": 10.044 + offset,
             "attrs": {"batch_id": 0, "request_ids": [7],
                       "trace_ids": [tid], "iters": 2,
                       "mesh": "nomesh", "policy": "f32"}},
            {"name": "serve_drain", "duration_ms": 180.0,
             "t_s": 10.049 + offset,
             "attrs": {"batch_id": 0, "request_ids": [7],
                       "trace_ids": [tid]}},
        ]
        self._dump(
            str(tmp_path / "router_flight" /
                "flight_router_drain_20260801T000000_0001.json"),
            router,
            context={"clock_offsets": {"1": offset}},
        )
        self._dump(
            str(tmp_path / "replica_1_flight" /
                "flight_preemption_drain_20260801T000000_0001.json"),
            replica,
        )
        return tid

    def test_trace_tree_spans_processes_with_nonnegative_hops(
        self, tmp_path
    ):
        """One request → ONE trace_id across router and replica records,
        replica timestamps translated through the handshake offset, and
        every per-hop delta non-negative."""
        from raft_ncup_tpu.observability import (
            collect_fleet_records,
            fleet_traces,
            render_trace,
        )

        tid = self._fleet_tree(tmp_path, offset=5.0)
        collected = collect_fleet_records(str(tmp_path))
        assert collected["clock_offsets"] == {1: 5.0}
        traces = fleet_traces(collected)
        assert len(traces) == 1
        tr = traces[0]
        assert tr["trace_id"] == tid
        assert tr["request_id"] == 7
        assert tr["origins"] == ["replica_1", "router"]
        assert tr["total_ms"] == 250.0
        # Translated timeline is ordered: root first, drain last.
        names = [r["name"] for r in tr["records"]]
        assert names[0] == "fleet_request"
        assert names.index("fleet_wire_hop") < names.index("serve_drain")
        hops = tr["hops"]
        for key in ("router_queue_ms", "wire_ms", "replica_queue_ms",
                    "device_ms", "return_ms"):
            assert key in hops, hops
            assert hops[key] >= 0.0
        assert hops["replica_queue_ms"] == 40.0
        assert hops["device_ms"] == 180.0
        assert hops["wire_ms"] == 2.0
        # total = hops + residual, exactly.
        assert sum(hops.values()) == pytest.approx(250.0)
        # Renderable without error, mentions both origins.
        text = "\n".join(render_trace(tr))
        assert "router" in text and "replica_1" in text

    def test_request_id_filter_and_skewed_offset_clamps(self, tmp_path):
        """A wrong offset estimate must clamp hops at zero, never go
        negative; the request_id filter narrows to one journey."""
        from raft_ncup_tpu.observability import (
            collect_fleet_records,
            fleet_traces,
        )

        self._fleet_tree(tmp_path, offset=5.0)
        collected = collect_fleet_records(str(tmp_path))
        # Sabotage the offset by a full second: the translated replica
        # records now precede the router's dispatch.
        collected["clock_offsets"][1] = 6.0
        traces = fleet_traces(collected, request_id=7)
        assert len(traces) == 1
        assert all(v >= 0.0 for v in traces[0]["hops"].values())
        assert fleet_traces(collected, request_id=999) == []

    def test_aggregate_registry_marks_dead_replica_gap(self, tmp_path):
        """The merged registry view SUMS counters and MAXES gauges over
        the replicas that exported, and NAMES the one that did not
        (dead replica ⇒ gap) instead of silently shrinking the fleet."""
        from raft_ncup_tpu.observability import aggregate_registry

        def snap(path, completed, depth):
            with open(path, "w") as fh:
                fh.write(json.dumps({
                    "name": "telemetry_snapshot",
                    "time_unix_s": 0.0,
                    "report": {"metrics": {
                        "counters": {"serve_completed_total": completed},
                        "gauges": {"serve_queue_depth":
                                   {"value": depth, "peak": depth + 1}},
                    }},
                }) + "\n")

        snap(tmp_path / "replica_0_telemetry.jsonl", 10, 2)
        snap(tmp_path / "replica_2_telemetry.jsonl", 32, 5)
        # Replica 1 existed (its socket path names it) but died without
        # an export.
        (tmp_path / "replica_1.sock").write_text("")
        agg = aggregate_registry(str(tmp_path))
        assert agg["counters"]["serve_completed_total"] == 42
        assert agg["gauges"]["serve_queue_depth"]["value"] == 5
        assert agg["gauges"]["serve_queue_depth"]["peak"] == 6
        assert agg["replicas"] == [0, 2]
        assert agg["gaps"] == [1]

    def test_aggregate_registry_tolerates_truncated_jsonl(self, tmp_path):
        from raft_ncup_tpu.observability import aggregate_registry

        p = tmp_path / "replica_0_telemetry.jsonl"
        with open(p, "w") as fh:
            fh.write(json.dumps({
                "name": "telemetry_snapshot",
                "report": {"metrics": {"counters": {"x_total": 3}}},
            }) + "\n")
            fh.write('{"name": "telemetry_snapsho')  # killed mid-write
        agg = aggregate_registry(str(tmp_path))
        assert agg["counters"] == {"x_total": 3}
        assert agg["skipped_lines"] == 1
        assert agg["gaps"] == []

    def test_collect_skips_torn_dump_falls_back_to_older(self, tmp_path):
        """The newest dump of a process may be torn (killed mid-write
        pre-os.replace never happens, but copies/foreign files do):
        collection walks back to the newest PARSABLE one and counts the
        skip."""
        from raft_ncup_tpu.observability import collect_fleet_records

        good = [{"name": "fleet_request", "duration_ms": 1.0,
                 "t_s": 0.0, "attrs": {"trace_id": "t", "request_id": 1}}]
        self._dump(
            str(tmp_path / "router_flight" /
                "flight_router_drain_20260801T000000_0001.json"),
            good,
        )
        torn = (tmp_path / "router_flight" /
                "flight_router_drain_20260801T000001_0002.json")
        torn.write_text('{"flight_recorder_version": 1, "spa')
        collected = collect_fleet_records(str(tmp_path))
        assert collected["skipped_dumps"] == 1
        assert [r["name"] for r in collected["origins"]["router"]] == [
            "fleet_request"
        ]
