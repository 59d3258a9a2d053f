"""Streaming video engine: lifecycle, isolation, eviction, chaos.

The robustness matrix docs/STREAMING.md documents, pinned end to end:
bounded stream admission (shed + retry-after), per-stream in-graph
anomaly reset with BITWISE batch-mate isolation, frame-gap staleness,
idle/abandoned eviction with recompile-free slot reuse, graceful
SIGTERM drain, and the sync-free/recompile-free steady state.
"""

import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import StreamConfig, small_model_config
from raft_ncup_tpu.models import get_model
from raft_ncup_tpu.resilience.chaos import ChaosSpec
from raft_ncup_tpu.serving.admission import AdmissionQueue
from raft_ncup_tpu.serving.request import (
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHED,
    TERMINAL_STATUSES,
)
from raft_ncup_tpu.streaming import (
    SlotRegistry,
    StreamEngine,
    StreamTraffic,
    replay_streams,
)

HW = (24, 32)  # h8=3, w8=4: tiny slot table, fast compiles


# ------------------------------------------------------------- test rigs


class _DummyVideoModel:
    """apply()-compatible stand-in whose flow depends on flow_init, so
    warm vs cold starts are observable without a RAFT compile. Rows are
    batch-independent (pure elementwise math), like the real model in
    test mode."""

    cfg = SimpleNamespace(hidden_dim=4)

    def apply(self, variables, image1, image2, iters=1, flow_init=None,
              test_mode=True, return_net=False, net_init=None,
              net_warm=None, **kw):
        B, H, W, _ = image1.shape
        h8, w8 = H // 8, W // 8
        lr = image1[:, ::8, ::8, :2] * 0.01
        if flow_init is not None:
            lr = lr + flow_init
        up = jnp.repeat(jnp.repeat(lr, 8, axis=1), 8, axis=2)
        net = jnp.full((B, h8, w8, 4), 0.5, jnp.float32)
        if net_init is not None:
            net = jnp.where(net_warm[:, None, None, None], net_init + 1.0,
                            net)
        if return_net:
            return lr, up, net
        return lr, up


def _img(seed=0, hw=HW):
    g = np.random.default_rng(seed)
    return (g.random((*hw, 3)) * 255.0).astype(np.float32)


def _scfg(**kw):
    base = dict(
        capacity=3,
        frame_hw=HW,
        iters=1,
        batch_sizes=(1, 2, 4),
        queue_capacity=8,
        idle_timeout_s=100.0,
    )
    base.update(kw)
    return StreamConfig(**base)


class FakeClock:
    def __init__(self, t0=1000.0):
        self.t = t0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _engine(**kw):
    clock = kw.pop("clock", time.monotonic)
    return StreamEngine(_DummyVideoModel(), {}, _scfg(**kw), clock=clock)


def _wait(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise TimeoutError(f"timed out waiting for {msg}")


# ----------------------------------------------------------- SlotRegistry


class TestSlotRegistry:
    def test_lowest_free_slot_first_and_deterministic_reuse(self):
        reg = SlotRegistry(3)
        assert reg.admit("a", HW, 0.0).slot == 0
        assert reg.admit("b", HW, 0.0).slot == 1
        assert reg.admit("c", HW, 0.0).slot == 2
        assert reg.admit("d", HW, 0.0) is None  # full
        assert reg.release("b") == 1
        assert reg.admit("e", HW, 1.0).slot == 1  # lowest freed slot
        assert reg.peak_occupancy == 3

    def test_evict_expired_skips_pending_and_orders_by_idle(self):
        reg = SlotRegistry(3)
        a = reg.admit("a", HW, 0.0)
        b = reg.admit("b", HW, 5.0)
        c = reg.admit("c", HW, 1.0)
        b.pending = 1  # in flight: not evictable
        evicted = reg.evict_expired(now=200.0, idle_timeout_s=100.0)
        assert [s.stream_id for s in evicted] == ["a", "c"]  # oldest first
        assert reg.get("b") is not None
        assert reg.evicted_total == 2

    def test_soonest_expiry_hint(self):
        reg = SlotRegistry(2)
        reg.admit("a", HW, 0.0)
        reg.admit("b", HW, 40.0)
        assert reg.soonest_expiry_s(now=50.0, idle_timeout_s=100.0) == 50.0
        assert reg.soonest_expiry_s(now=150.0, idle_timeout_s=100.0) == 0.0


# ----------------------------------------- AdmissionQueue distinct popping


class TestPopBatchDistinct:
    def _req(self, rid, stream, key="a"):
        return SimpleNamespace(request_id=rid, stream=stream, shape_key=key)

    def test_skips_same_stream_in_place(self):
        q = AdmissionQueue(capacity=8)
        for rid, s in enumerate(["A", "A", "B", "C"]):
            q.offer(self._req(rid, s))
        batch = q.pop_batch(
            4, key_fn=lambda r: r.shape_key,
            distinct_fn=lambda r: r.stream,
        )
        assert [(r.request_id, r.stream) for r in batch] == [
            (0, "A"), (2, "B"), (3, "C"),
        ]
        # The duplicate kept its position (and per-stream FIFO order).
        rest = q.pop_batch(4, key_fn=lambda r: r.shape_key,
                           distinct_fn=lambda r: r.stream)
        assert [(r.request_id, r.stream) for r in rest] == [(1, "A")]

    def test_stops_at_different_key(self):
        q = AdmissionQueue(capacity=8)
        q.offer(self._req(0, "A", key="x"))
        q.offer(self._req(1, "B", key="y"))
        q.offer(self._req(2, "C", key="x"))
        batch = q.pop_batch(
            4, key_fn=lambda r: r.shape_key,
            distinct_fn=lambda r: r.stream,
        )
        # Never reorders across shape keys: C stays behind B.
        assert [r.request_id for r in batch] == [0]
        assert len(q) == 2

    def test_respects_max_n(self):
        q = AdmissionQueue(capacity=8)
        for rid, s in enumerate(["A", "B", "C"]):
            q.offer(self._req(rid, s))
        batch = q.pop_batch(2, key_fn=lambda r: r.shape_key,
                            distinct_fn=lambda r: r.stream)
        assert [r.request_id for r in batch] == [0, 1]


# ------------------------------------------------------- chaos + traffic


class TestStreamChaos:
    def test_spec_round_trip_with_stream_kinds(self):
        spec = ChaosSpec.parse("corruptframe@3,abandon@7,burst@2,sigterm@9")
        assert spec.corrupt_frames == frozenset({3})
        assert spec.abandon_frames == frozenset({7})
        assert spec.burst_requests == frozenset({2})
        assert spec.sigterm_after == 9
        assert spec.active
        assert ChaosSpec.parse(spec.render()) == spec

    def test_bad_kind_rejected(self):
        with pytest.raises(ValueError):
            ChaosSpec.parse("corruptedframe@3")

    def test_corruptframe_is_nan_and_only_that_frame(self):
        chaos = ChaosSpec.parse("corruptframe@4")
        frames = list(StreamTraffic(HW, 3, 2, seed=1, chaos=chaos))
        # Emission order: (f0: s0 s1 s2), (f1: s0 s1 s2) -> index 4 is
        # stream-1's frame 1.
        assert frames[4][1] == "stream-1" and frames[4][2] == 1
        assert np.isnan(frames[4][3]).all()
        assert not any(
            np.isnan(f[3]).any() for i, f in enumerate(frames) if i != 4
        )

    def test_abandon_truncates_stream(self):
        chaos = ChaosSpec.parse("abandon@1")  # stream-1's frame 0
        frames = list(StreamTraffic(HW, 3, 3, seed=1, chaos=chaos))
        by_stream = {}
        for _, sid, f, _, _ in frames:
            by_stream.setdefault(sid, []).append(f)
        assert by_stream["stream-1"] == [0]  # nothing after the abandon
        assert by_stream["stream-0"] == [0, 1, 2]
        assert by_stream["stream-2"] == [0, 1, 2]

    def test_burst_adds_one_frame_streams(self):
        chaos = ChaosSpec.parse("burst@2")
        frames = list(
            StreamTraffic(HW, 2, 2, seed=1, chaos=chaos, burst_size=3)
        )
        burst = [f for f in frames if f[1].startswith("burst-")]
        assert len(burst) == 3
        steady = [f for f in frames if not f[1].startswith("burst-")]
        assert len(steady) == 4

    def test_schedule_is_deterministic(self):
        a = list(StreamTraffic(HW, 2, 3, seed=9))
        b = list(StreamTraffic(HW, 2, 3, seed=9))
        for (da, sa, fa, i1a, i2a), (db, sb, fb, i1b, i2b) in zip(a, b):
            assert (da, sa, fa) == (db, sb, fb)
            np.testing.assert_array_equal(i1a, i1b)
            np.testing.assert_array_equal(i2a, i2b)


# ------------------------------------------------------ engine lifecycle


class TestEngineLifecycle:
    def test_frames_complete_with_native_unpad_and_auto_index(self):
        eng = _engine()
        try:
            rs = []
            for f in range(3):  # auto frame indices
                rs.append(eng.submit("s0", _img(f), _img(f + 10)).result(30))
            assert [r.status for r in rs] == [STATUS_OK] * 3
            assert rs[0].flow.shape == (*HW, 2)
            assert rs[0].iters == 1
            with eng._reg_lock:
                state = eng.registry.get("s0")
                assert state.last_frame_index == 2
                assert state.frames_completed == 3
        finally:
            eng.drain()
        assert eng.stats.cold_starts == 1  # only the first frame

    def test_warm_start_changes_output_and_gap_forces_cold(self):
        """Frame 2 warm-started differs from the same images computed
        cold; a frame-index gap beyond max_frame_gap forces the cold
        result bitwise (never a stale warm start)."""
        img1, img2 = _img(100), _img(101)

        def run(indices):
            eng = _engine(capacity=1, batch_sizes=(1,))
            try:
                out = [
                    eng.submit("s", img1, img2, frame_index=i).result(30)
                    for i in indices
                ]
            finally:
                eng.drain()
            return out

        warm = run([0, 1])  # consecutive: frame 1 warm-starts
        gap = run([0, 5])  # gap > max_frame_gap: frame 5 forced cold
        cold = run([0])  # reference cold result for these images
        assert warm[1].ok and gap[1].ok
        assert not np.array_equal(
            np.asarray(warm[1].flow), np.asarray(cold[0].flow)
        )
        np.testing.assert_array_equal(
            np.asarray(gap[1].flow), np.asarray(cold[0].flow)
        )

    def test_stream_admission_sheds_at_capacity_with_retry_hint(self):
        clock = FakeClock()
        eng = _engine(capacity=2, clock=clock, idle_timeout_s=50.0)
        try:
            assert eng.submit("a", _img(1), _img(2)).result(30).ok
            assert eng.submit("b", _img(3), _img(4)).result(30).ok
            r = eng.submit("c", _img(5), _img(6)).result(30)
            assert r.status == STATUS_SHED
            assert r.detail == "stream table full"
            assert r.retry_after_s == 50.0  # soonest idle expiry
            assert eng.stats.shed_streams == 1
        finally:
            eng.drain()

    def test_frame_queue_full_sheds(self):
        eng = _engine(capacity=4, queue_capacity=2)
        try:
            eng.pause()
            h1 = eng.submit("a", _img(1), _img(2))
            h2 = eng.submit("b", _img(3), _img(4))
            h3 = eng.submit("c", _img(5), _img(6))
            r3 = h3.result(1)
            assert r3.status == STATUS_SHED
            assert r3.detail == "frame queue full"
            eng.resume()
            assert h1.result(30).ok and h2.result(30).ok
        finally:
            eng.drain()

    def test_malformed_and_out_of_order_rejected(self):
        eng = _engine()
        try:
            r = eng.submit("a", _img(1)[:, :, :2], _img(2)).result(1)
            assert r.status == STATUS_REJECTED and "(H, W, 3)" in r.detail
            # Wrong padded shape for this engine's slot table:
            big = _img(1, (64, 64))
            r = eng.submit("a", big, big).result(1)
            assert r.status == STATUS_REJECTED and "slot table" in r.detail
            assert eng.submit("a", _img(1), _img(2),
                              frame_index=5).result(30).ok
            r = eng.submit("a", _img(3), _img(4), frame_index=5).result(1)
            assert r.status == STATUS_REJECTED
            assert "out-of-order" in r.detail
            # A shed/rejected frame must not have advanced the index.
            with eng._reg_lock:
                assert eng.registry.get("a").last_frame_index == 5
        finally:
            eng.drain()

    def test_mid_stream_resolution_change_rejected(self):
        eng = _engine(pad_bucket=32, frame_hw=(24, 32))
        try:
            assert eng.submit("a", _img(1), _img(2)).result(30).ok
            # (26, 30) pads into the same 32x32-bucketed table but the
            # stream was opened at (24, 32): per-stream shape is fixed.
            other = _img(3, (26, 30))
            r = eng.submit("a", other, other).result(1)
            assert r.status == STATUS_REJECTED and "stream 'a' is" in r.detail
        finally:
            eng.drain()

    def test_close_stream_frees_slot_for_reuse(self):
        eng = _engine(capacity=1)
        try:
            assert eng.submit("a", _img(1), _img(2)).result(30).ok
            assert eng.close_stream("a")
            assert not eng.close_stream("nope")
            # Slot freed: a new stream admits immediately.
            assert eng.submit("b", _img(3), _img(4)).result(30).ok
            assert eng.stats.streams_closed == 1
        finally:
            eng.drain()

    def test_idle_eviction_frees_slot_and_reuse_has_no_recompile(self):
        """The abandoned-stream path: after idle_timeout the slot frees
        (dispatcher idle tick), a new stream reuses it, and NOTHING
        recompiles — the executable set was fixed at warmup."""
        clock = FakeClock()
        eng = _engine(capacity=1, clock=clock, idle_timeout_s=10.0)
        try:
            eng.warmup()
            compiles = eng._fwd.stats["compiles"]
            assert eng.submit("a", _img(1), _img(2)).result(30).ok
            clock.advance(11.0)
            _wait(
                lambda: eng.registry.occupancy == 0,
                msg="idle eviction by dispatcher tick",
            )
            r = eng.submit("b", _img(3), _img(4)).result(30)
            assert r.ok
            assert eng.stats.streams_evicted == 1
            assert eng._fwd.stats["compiles"] == compiles  # no recompile
            rep = eng.report()
            assert rep["evicted"] == 1 and rep["capacity"] == 1
        finally:
            eng.drain()

    def test_eviction_never_takes_streams_with_frames_in_flight(self):
        clock = FakeClock()
        eng = _engine(capacity=1, clock=clock, idle_timeout_s=10.0)
        try:
            eng.pause()  # keep the frame pending
            h = eng.submit("a", _img(1), _img(2))
            clock.advance(100.0)
            time.sleep(0.2)  # several dispatcher idle ticks
            with eng._reg_lock:
                assert eng.registry.get("a") is not None
            eng.resume()
            assert h.result(30).ok
        finally:
            eng.drain()

    def test_drain_flushes_admitted_sheds_new_and_is_idempotent(self):
        eng = _engine()
        eng.pause()
        hs = [
            eng.submit(f"s{i}", _img(i), _img(i + 10)) for i in range(3)
        ]
        eng.resume()
        stats = eng.drain()
        assert [h.result(1).status for h in hs] == [STATUS_OK] * 3
        late = eng.submit("s9", _img(9), _img(19)).result(1)
        assert late.status == STATUS_SHED and late.detail == "draining"
        assert eng.drain() is stats  # idempotent
        # No silent drops: every submission reached a terminal status.
        assert stats.completed == 3
        assert stats.submitted == 4

    def test_burst_of_streams_sheds_explicitly(self):
        chaos = ChaosSpec.parse("burst@1")  # after both steady admits
        eng = _engine(capacity=2)
        try:
            traffic = StreamTraffic(
                HW, 2, 2, seed=3, chaos=chaos, burst_size=4
            )
            handles, _ = replay_streams(eng, traffic)
            rs = [h.result(30) for h in handles]
        finally:
            eng.drain()
        assert all(r.status in TERMINAL_STATUSES for r in rs)
        # 2 steady streams fill the table; all 4 burst streams shed.
        shed = [r for r in rs if r.status == STATUS_SHED]
        assert len(shed) == 4
        assert all(r.retry_after_s is not None for r in shed)
        ok = [r for r in rs if r.ok]
        assert len(ok) == 4  # both steady streams' frames all served


# ------------------------------------------------- carry_net (GRU state)


# ------------------------------------------- the no-silent-loss contract
#
# The engine's half of what tests/test_serving.py holds the server to
# (TestServerErrorPath, TestDrainWorkerFailure, TestBatchStageSpans): a
# fault of the engine's own answers every admitted frame `error` and
# releases what the frame held, and the batch's stage spans tile the
# externally timed ``stream_drain``.

_BATCH_STAGES = (
    "stream_dispatch", "stream_throttle_wait", "stream_device_wait",
    "stream_pull", "stream_deliver",
)


class _TickClock:
    """Every read is one second after the last, from any thread; ``log``
    keeps which thread read which second."""

    def __init__(self):
        self._t, self._lock, self.log = 0.0, threading.Lock(), []

    def __call__(self) -> float:
        with self._lock:
            self._t += 1.0
            self.log.append((self._t, threading.get_ident()))
            return self._t


def _stage_records(tel, batch_id):
    out = {}
    for name in _BATCH_STAGES + ("stream_drain",):
        (rec,) = [
            r for r in tel.tracer.records(name)
            if r["attrs"]["batch_id"] == batch_id
        ]
        out[name] = (rec["t_s"], rec["t_s"] + rec["duration_ms"] / 1e3)
    return out


class TestEngineErrorPath:
    def test_forward_failure_answers_the_batch_and_engine_survives(self):
        """A forward that raises is the engine's fault, not the
        client's: every frame of the batch answers `error`, the frames'
        pending counts are released (a stream closed meanwhile gets its
        slot back), and the dispatcher serves the next batch."""

        class FlakyVideoModel(_DummyVideoModel):
            fail = True

            def apply(self, *a, **kw):
                if self.fail:
                    raise ValueError("boom")
                return super().apply(*a, **kw)

        model = FlakyVideoModel()
        eng = StreamEngine(model, {}, _scfg())
        try:
            eng.pause()
            ha = eng.submit("a", _img(1), _img(2))
            hb = eng.submit("b", _img(3), _img(4))
            eng.close_stream("b")  # deferred: its frame is pending
            eng.resume()
            ra, rb = ha.result(30), hb.result(30)
            assert ra.status == "error" and "boom" in ra.detail
            assert rb.status == "error" and "boom" in rb.detail
            with eng._reg_lock:
                assert eng.registry.get("a").pending == 0
                assert eng.registry.get("b") is None  # slot released
            model.fail = False
            assert eng.submit("a", _img(5), _img(6)).result(30).ok
        finally:
            stats = eng.drain()
        assert stats.errors == 2 and stats.completed == 1
        assert stats.batches == 2 and stats.streams_closed == 1
        assert eng._handles == {} and eng._inflight == {}


class TestEngineDrainWorkerFailure:
    def test_stranded_batches_flushed_and_drain_returns_the_stats(self):
        """AsyncDrain surfaces a worker error from a LATER submit or from
        close(): the in-flight registry must answer the batches the
        worker stranded (with a drain-failure detail) on either path, and
        drain() still returns the stats with nothing admitted lost."""

        class AsyncDeadDrainer:
            calls = 0

            def submit(self, tree, cb, span=None):
                self.calls += 1
                if self.calls == 2:
                    raise RuntimeError("pull failed")
                # calls 1 and 3: accepted; the worker dies before
                # delivering.

            def close(self):
                raise RuntimeError("worker died")

        eng = _engine(batch_sizes=(1,))
        eng._drainer = AsyncDeadDrainer()
        ha = eng.submit("a", _img(1), _img(2))  # batch 1: stranded
        hb = eng.submit("b", _img(3), _img(4))  # batch 2: submit raises
        ra, rb = ha.result(30), hb.result(30)
        assert ra.status == "error" and "result drain failed" in ra.detail
        assert rb.status == "error" and "pull failed" in rb.detail
        hc = eng.submit("a", _img(5), _img(6))  # batch 3: stranded
        _wait(lambda: eng._inflight, msg="batch 3 in flight")
        stats = eng.drain(timeout=30)  # close() raises: batch 3 flushed
        rc = hc.result(1)
        assert rc.status == "error" and "worker died" in rc.detail
        assert stats is eng.stats
        assert stats.submitted == 3 == stats.errors
        assert stats.completed == 0
        assert eng._handles == {} and eng._inflight == {}
        with eng._reg_lock:
            assert eng.registry.get("a").pending == 0
            assert eng.registry.get("b").pending == 0


class TestEngineBatchStageSpans:
    """``stream_dispatch`` is the copy and the jit dispatch alone; the
    throttle's wait and the drain worker's device wait, pull and deliver
    are spans of their own under the batch's id. With the wait in the
    drainer's queue they tile the externally timed ``stream_drain``."""

    def test_stages_tile_stream_drain_under_an_injected_clock(self):
        from raft_ncup_tpu.observability import Telemetry

        clock = _TickClock()
        tel = Telemetry(clock=clock)
        eng = StreamEngine(
            _DummyVideoModel(), {}, _scfg(batch_sizes=(2,)), clock=clock,
            telemetry=tel,
        )
        try:
            eng.warmup()  # the compile's own phases read the clock too
            eng.pause()
            h1 = eng.submit("a", _img(1), _img(2))
            h2 = eng.submit("b", _img(3), _img(4))
            eng.resume()
            assert h1.result(60).ok and h2.result(60).ok
        finally:
            eng.drain()
        st = _stage_records(tel, batch_id=0)
        order = [st[name] for name in _BATCH_STAGES]
        # disjoint, in this order, each one tick long
        for (s0, e0), (s1, e1) in zip(order, order[1:]):
            assert s0 < e0 < s1 < e1
        assert [e - s for s, e in order[:4]] == [1.0] * 4
        # stream_drain runs from the read before stream_dispatch to the
        # read at the top of deliver; observe_ms reads the clock once
        # more to place it, hence the tick taken off its end.
        drain_start = st["stream_drain"][0] - 1.0
        drain_end = st["stream_drain"][1] - 1.0
        assert drain_start == st["stream_dispatch"][0] - 1.0
        assert st["stream_pull"][1] < drain_end
        assert st["stream_deliver"][0] < drain_end < st["stream_deliver"][1]
        covered = sum(e - s for s, e in order[:4])
        # What the four spans leave of stream_drain is clock reads alone:
        # 2 per span boundary pair, plus deliver's start and `done`, plus
        # whatever the dispatcher read for its NEXT assembly once it had
        # handed this batch to the drain worker.
        handed_over = st["stream_throttle_wait"][1]
        dispatcher = eng._thread.ident
        next_assembly = [
            t for t, thread in clock.log
            if thread == dispatcher and handed_over < t < drain_end
        ]
        assert (drain_end - drain_start) - covered - len(
            next_assembly
        ) == pytest.approx(6.0)

    def test_every_batch_has_each_stage_once_inside_its_drain(self):
        from raft_ncup_tpu.observability import Telemetry

        tel = Telemetry()
        eng = StreamEngine(_DummyVideoModel(), {}, _scfg(), telemetry=tel)
        try:
            # One stream: its frames never share a batch.
            handles = [
                eng.submit("s", _img(i), _img(i + 1)) for i in range(5)
            ]
            assert all(h.result(60).ok for h in handles)
        finally:
            stats = eng.drain()
        batches = {
            r["attrs"]["batch_id"] for r in tel.tracer.records("stream_drain")
        }
        assert len(batches) == stats.batches == 5
        for b in batches:
            st = _stage_records(tel, b)  # exactly one record a stage
            order = [st[name] for name in _BATCH_STAGES]
            slack = 2e-3  # records round to microseconds and 1e-3 ms
            for (s0, e0), (s1, e1) in zip(order, order[1:]):
                assert s0 <= e0 <= s1 + slack and s1 <= e1
            d0, d1 = st["stream_drain"]
            assert d0 - slack <= order[0][0] and order[3][1] <= d1 + slack
        report = eng.report()["stages"]
        assert set(_BATCH_STAGES) <= set(report)
        assert report["stream_pull"]["count"] == stats.batches


class TestCarryNet:
    def test_net_carried_only_when_enabled_and_warm(self):
        img1, img2 = _img(200), _img(201)

        def second_frame(carry_net):
            eng = _engine(capacity=1, batch_sizes=(1,),
                          carry_net=carry_net)
            try:
                eng.submit("s", img1, img2).result(30)
                return eng.submit("s", img1, img2).result(30)
            finally:
                eng.drain()

        with_net = second_frame(True)
        without = second_frame(False)
        assert with_net.ok and without.ok
        # The dummy model folds net_init into nothing visible in flow,
        # so compare table state instead: carry_net allocates the net
        # plane and stores non-zero state after a good frame.
        eng = _engine(capacity=1, batch_sizes=(1,), carry_net=True)
        try:
            assert "net" in eng._table
            eng.submit("s", img1, img2).result(30)
            _wait(lambda: not eng._handles, msg="delivery")
            net = np.asarray(jax.device_get(eng._table["net"]))
            assert np.any(net[0] != 0)
        finally:
            eng.drain()
        eng2 = _engine(capacity=1, batch_sizes=(1,), carry_net=False)
        try:
            assert "net" not in eng2._table
        finally:
            eng2.drain()


# ------------------------------------------- real model: isolation matrix


@pytest.fixture(scope="module")
def tiny_model():
    cfg = small_model_config("raft", dataset="chairs")
    model = get_model(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, *HW, 3))
    return model, variables


def _run_rounds(model, variables, frames, *, corrupt=None, skip=None,
                scfg=None):
    """Drive the engine in deterministic rounds (pause → one frame per
    stream → resume), returning {(stream, frame): response}.

    ``corrupt``: (stream_id, frame) whose first image is NaN.
    ``skip``: (stream_id, first_frame) — the stream only joins at
    first_frame (the same-batch-composition cold reference).
    """
    eng = StreamEngine(model, variables, scfg or _scfg(capacity=4))
    out = {}
    try:
        eng.warmup()
        n_frames = max(f for (_, f) in frames) + 1
        streams = sorted({s for (s, _) in frames})
        for f in range(n_frames):
            eng.pause()
            hs = []
            for sid in streams:
                if (sid, f) not in frames:
                    continue
                if skip and sid == skip[0] and f < skip[1]:
                    continue
                i1, i2 = frames[(sid, f)]
                if corrupt and (sid, f) == corrupt:
                    i1 = np.full(i1.shape, np.nan, np.float32)
                hs.append(((sid, f), eng.submit(sid, i1, i2,
                                                frame_index=f)))
            eng.resume()
            for k, h in hs:
                out[k] = h.result(120)
        stats = eng.stats
    finally:
        eng.drain()
    return out, stats


@pytest.mark.slow
class TestIsolationRealModel:
    N_STREAMS, N_FRAMES = 3, 3
    CORRUPT = ("stream-1", 1)

    def _frames(self):
        frames = {}
        for _, sid, f, i1, i2 in StreamTraffic(
            HW, self.N_STREAMS, self.N_FRAMES, seed=5
        ):
            frames[(sid, f)] = (i1, i2)
        return frames

    def test_corruptframe_isolation_bitwise(self, tiny_model):
        """The acceptance pin: under corruptframe chaos the corrupted
        stream resets to cold start while every co-batched stream's
        output flow is BITWISE identical to an uninjected run, in the
        corrupted frame's batch and every batch after it."""
        model, variables = tiny_model
        frames = self._frames()
        base, _ = _run_rounds(model, variables, frames)
        cha, stats = _run_rounds(
            model, variables, frames, corrupt=self.CORRUPT
        )

        # 1) batch-mates bitwise identical, every frame.
        for (sid, f), r in base.items():
            if sid == self.CORRUPT[0]:
                continue
            rc = cha[(sid, f)]
            assert r.ok and rc.ok
            np.testing.assert_array_equal(
                np.asarray(r.flow), np.asarray(rc.flow),
                err_msg=f"batch-mate {sid} frame {f} diverged",
            )

        # 2) the corrupted frame answers `rejected` with the anomaly
        #    detail; nothing was silently dropped.
        bad = cha[self.CORRUPT]
        assert bad.status == STATUS_REJECTED
        assert "anomaly" in bad.detail
        assert stats.resets == 1

        # 3) the corrupted stream's NEXT frame is bitwise a cold start:
        #    same batch composition, stream joining cold at that frame.
        ref, _ = _run_rounds(
            model, variables, frames,
            skip=(self.CORRUPT[0], self.CORRUPT[1] + 1),
        )
        for f in range(self.CORRUPT[1] + 1, self.N_FRAMES):
            np.testing.assert_array_equal(
                np.asarray(cha[(self.CORRUPT[0], f)].flow),
                np.asarray(ref[(self.CORRUPT[0], f)].flow),
                err_msg=f"post-reset frame {f} is not a cold start",
            )

    def test_steady_state_sync_free_recompile_free(
        self, tiny_model, forbid_host_transfers, max_recompiles
    ):
        """The invariant the bench row records: after warmup, a steady
        multi-stream window performs ZERO implicit host pulls and ZERO
        compiles; each batch's flow+flags pull is the one sanctioned
        explicit device_get in the AsyncDrain worker. Warm-starting,
        cold-starting, and slot scatter all ride the same programs."""
        model, variables = tiny_model
        eng = StreamEngine(model, variables, _scfg(capacity=2))
        try:
            eng.warmup()
            # Warm the pipeline (first frames of both streams).
            eng.pause()
            hs = [eng.submit(s, _img(7), _img(8)) for s in ("a", "b")]
            eng.resume()
            assert all(h.result(120).ok for h in hs)
            with forbid_host_transfers() as stats, max_recompiles(0):
                for _ in range(2):
                    eng.pause()
                    hs = [
                        eng.submit(s, _img(9), _img(10))
                        for s in ("a", "b")
                    ]
                    eng.resume()
                    rs = [h.result(120) for h in hs]
                    assert [r.status for r in rs] == [STATUS_OK] * 2
        finally:
            eng.drain()
        assert stats.host_transfers == 0
        assert stats.sanctioned_gets == 2  # one per dispatched batch

    def test_sigterm_mid_window_drains_all_admitted(self, tiny_model):
        """The drain contract under a REAL signal through the real
        handler: submission stops, every admitted frame is flushed
        through compute, nothing is silently dropped."""
        from raft_ncup_tpu.resilience import PreemptionHandler

        model, variables = tiny_model
        eng = StreamEngine(model, variables, _scfg(capacity=4))
        try:
            eng.warmup()
            traffic = StreamTraffic(HW, 2, 4, seed=6)
            with PreemptionHandler() as preempt:
                handles, interrupted = replay_streams(
                    eng, traffic, preempt=preempt, sigterm_after=3
                )
            stats = eng.drain()
        finally:
            eng.drain()
        assert interrupted
        assert len(handles) == 3  # stopped right after the signal
        rs = [h.result(30) for h in handles]
        assert [r.status for r in rs] == [STATUS_OK] * 3
        assert stats.completed == 3
        assert stats.errors == 0


class TestUhdAdmissibility:
    """4K (2176x3840) is a valid engine shape (docs/PERF.md "Banded
    dispatch"): the config validates, the slot table allocates, warmup
    compiles the executable set, and a re-warm is ALL LRU hits — no
    recompile on reuse. The dummy model sidesteps a RAFT compile, but
    warmup still EXECUTES the in-graph warm-start splat at 272x480
    slot resolution — real minutes-scale CPU work, hence the slow
    marker on the warmup test; the real-model 4K evidence is
    scripts/highres_forward.py + the residency pins in
    tests/test_pallas_lowering.py."""

    def test_4k_stream_config_is_admissible(self):
        cfg = _scfg(frame_hw=(2176, 3840), capacity=1, batch_sizes=(1,))
        assert cfg.frame_hw == (2176, 3840)
        # /8-clean: the padded slot-table shape IS the native shape.
        assert cfg.frame_hw[0] % 8 == 0 and cfg.frame_hw[1] % 8 == 0

    @pytest.mark.slow
    def test_4k_engine_warms_without_recompile_on_reuse(self):
        eng = _engine(frame_hw=(2176, 3840), capacity=1,
                      batch_sizes=(1,), queue_capacity=2)
        try:
            compiled = eng.warmup()
            assert compiled >= 1
            assert (2176, 3840, 1, eng.cfg.iters) in [
                (h, w, b, i) for (h, w, b, i) in eng.warmed
            ]
            before = dict(eng._fwd.stats)
            assert eng.warmup() == 0  # re-warm: pure LRU hits
            after = eng._fwd.stats
            assert after["compiles"] == before["compiles"]
            assert after["evictions"] == before["evictions"]
        finally:
            eng.drain(timeout=120.0)
