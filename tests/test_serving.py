"""Online serving subsystem (raft_ncup_tpu/serving/): admission/shedding
semantics, iteration-budget hysteresis, deterministic traffic, poison
quarantine with batch-mate isolation, deadline handling, graceful drain
on SIGTERM, and the sync-free/recompile-free steady state under the
runtime guards — the chaos matrix of docs/SERVING.md, end to end.
"""

from __future__ import annotations

import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from types import SimpleNamespace

from raft_ncup_tpu.config import ServeConfig, small_model_config
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.resilience import PreemptionHandler
from raft_ncup_tpu.resilience.chaos import ChaosSpec
from raft_ncup_tpu.serving import (
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHED,
    STATUS_TIMEOUT,
    TERMINAL_STATUSES,
    AdmissionQueue,
    FlowRequest,
    FlowServer,
    IterationBudgetController,
    ServeHandle,
    SyntheticTraffic,
    replay,
)
from raft_ncup_tpu.serving.request import FlowResponse


# ------------------------------------------------------------- test rigs


class _DummyModel:
    """apply()-compatible stand-in: the 'flow' is a deterministic
    function of image1 AND the iteration count, so responses prove which
    budget level computed them without a RAFT compile."""

    def apply(self, variables, image1, image2, iters=1, flow_init=None,
              test_mode=True, mesh=None, metric_head=None, **kw):
        flow_up = jnp.stack(
            [image1[..., 0] * iters, image1[..., 1]], axis=-1
        )
        return image1.mean(), flow_up


def _img(seed=0, hw=(24, 32)):
    g = np.random.default_rng(seed)
    return (g.random((*hw, 3)) * 255.0).astype(np.float32)


def _cfg(**kw):
    base = dict(
        queue_capacity=8,
        batch_sizes=(1, 2),
        iter_levels=(4, 2),
        high_water=0.75,
        low_water=0.25,
        recover_patience=2,
    )
    base.update(kw)
    return ServeConfig(**base)


def _server(**kw) -> FlowServer:
    return FlowServer(_DummyModel(), {}, _cfg(**kw))


def _wait_idle(server, timeout=10.0):
    """Block until everything admitted so far has terminated."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not server._handles and not len(server._queue):
            return
        time.sleep(0.01)
    raise TimeoutError("server did not go idle")


# -------------------------------------------------------- AdmissionQueue


class TestAdmissionQueue:
    def _req(self, rid, key="a"):
        return FlowRequest(rid, None, None, shape_key=key)

    def test_offer_sheds_at_capacity(self):
        q = AdmissionQueue(capacity=3)
        assert all(q.offer(self._req(i)) for i in range(3))
        assert not q.offer(self._req(3))
        assert len(q) == 3

    def test_pop_batch_groups_fifo_runs_by_key(self):
        q = AdmissionQueue(capacity=10)
        for rid, key in enumerate("aabba"):
            q.offer(self._req(rid, key))
        batches = []
        while len(q):
            batches.append([r.request_id for r in q.pop_batch(4)])
        # Grouping never reorders across a key change: the trailing 'a'
        # must NOT jump the 'b' run.
        assert batches == [[0, 1], [2, 3], [4]]

    def test_pop_batch_respects_max_n(self):
        q = AdmissionQueue(capacity=10)
        for rid in range(5):
            q.offer(self._req(rid))
        assert len(q.pop_batch(2)) == 2
        assert len(q) == 3

    def test_closed_queue_sheds_but_drains(self):
        q = AdmissionQueue(capacity=4)
        q.offer(self._req(0))
        q.close()
        assert not q.offer(self._req(1))  # no new admissions
        assert [r.request_id for r in q.pop_batch(4)] == [0]  # drainable
        assert q.pop_batch(4) == []  # closed + empty = exit signal

    def test_pop_batch_times_out_empty(self):
        q = AdmissionQueue(capacity=2)
        t0 = time.monotonic()
        assert q.pop_batch(2, timeout=0.05) == []
        assert time.monotonic() - t0 < 1.0


# ------------------------------------------------- IterationBudgetController


class TestBudgetController:
    def _ctl(self, **kw):
        base = dict(levels=(24, 16, 8), capacity=8, high_water=0.75,
                    low_water=0.25, recover_patience=2)
        base.update(kw)
        return IterationBudgetController(**base)

    def test_degrades_immediately_at_high_water(self):
        ctl = self._ctl()
        assert ctl.decide(0) == 24
        assert ctl.decide(6) == 16  # 0.75 occupancy: one level, now
        assert ctl.decide(8) == 8  # saturated: next level
        assert ctl.decide(8) == 8  # floor: stays at the coarsest
        assert ctl.drops == 2

    def test_recovery_needs_sustained_calm(self):
        ctl = self._ctl()
        ctl.decide(8)  # -> 16
        assert ctl.iters == 16
        assert ctl.decide(1) == 16  # calm 1: not yet
        assert ctl.decide(1) == 24  # calm 2 = patience: recover
        assert ctl.recoveries == 1

    def test_mid_band_resets_patience(self):
        """Load oscillating through the low band must not recover: the
        calm streak restarts whenever occupancy leaves it."""
        ctl = self._ctl()
        ctl.decide(8)  # -> 16
        ctl.decide(1)  # calm 1
        ctl.decide(4)  # mid-band (0.5): streak reset
        assert ctl.decide(1) == 16  # calm 1 again — no recovery
        assert ctl.decide(1) == 24
        assert (ctl.drops, ctl.recoveries) == (1, 1)

    def test_full_burst_trajectory(self):
        """The documented drain-a-burst trajectory: saturate, walk down,
        hold through the mid band, recover after sustained calm."""
        ctl = self._ctl(levels=(4, 2), recover_patience=2)
        depths = [8, 7, 6, 5, 4, 3, 2, 1]
        iters = [ctl.decide(d) for d in depths]
        assert iters == [2, 2, 2, 2, 2, 2, 2, 4]
        assert (ctl.drops, ctl.recoveries) == (1, 1)
        assert ctl.decisions == [1, 7]

    def test_validation(self):
        with pytest.raises(ValueError, match="descending"):
            self._ctl(levels=(8, 16))
        with pytest.raises(ValueError, match="positive"):
            self._ctl(levels=(8, 0))
        with pytest.raises(ValueError, match="low_water"):
            self._ctl(low_water=0.8)

    def test_serve_config_validation(self):
        with pytest.raises(ValueError, match="batch_sizes"):
            ServeConfig(batch_sizes=(2, 1))
        with pytest.raises(ValueError, match="iter_levels"):
            ServeConfig(iter_levels=(8, 8))


# ----------------------------------------------------------- ServeHandle


class TestHandleAndStats:
    def test_handle_completes_once(self):
        h = ServeHandle()
        h.complete(FlowResponse(0, STATUS_OK))
        with pytest.raises(RuntimeError, match="twice"):
            h.complete(FlowResponse(0, STATUS_OK))
        assert h.result(0.1).ok

    def test_result_timeout(self):
        with pytest.raises(TimeoutError):
            ServeHandle().result(timeout=0.01)


# ------------------------------------------------------------ traffic


class TestSyntheticTraffic:
    def test_deterministic_and_ordered(self):
        mk = lambda: list(SyntheticTraffic((8, 10), 4, seed=3,
                                           interval_s=0.5))
        a, b = mk(), mk()
        assert [x[0] for x in a] == [0.0, 0.5, 1.0, 1.5]
        for (_, i1, i2), (_, j1, j2) in zip(a, b):
            np.testing.assert_array_equal(i1, j1)
            np.testing.assert_array_equal(i2, j2)

    def test_burst_expands_request(self):
        chaos = ChaosSpec.parse("burst@1")
        tr = SyntheticTraffic((8, 10), 3, seed=0, interval_s=1.0,
                              burst_size=4, chaos=chaos)
        events = list(tr)
        assert len(events) == len(tr) == 6  # 3 + (4 - 1)
        assert [e[0] for e in events] == [0.0, 1.0, 1.0, 1.0, 1.0, 2.0]

    def test_len_ignores_bursts_past_stream_end(self):
        # burst@5 on a 3-request stream never fires: len must agree
        # with what __iter__ actually emits.
        chaos = ChaosSpec.parse("burst@5")
        tr = SyntheticTraffic((8, 10), 3, seed=0, burst_size=4,
                              chaos=chaos)
        assert len(list(tr)) == len(tr) == 3

    def test_poison_event_is_nan(self):
        chaos = ChaosSpec.parse("poison@2")
        events = list(SyntheticTraffic((8, 10), 3, seed=0, chaos=chaos))
        assert np.isnan(events[2][1]).all()
        assert not np.isnan(events[1][1]).any()

    def test_chaos_spec_round_trip(self):
        spec = ChaosSpec.parse("burst@4,poison@7,sigterm@9")
        assert spec.burst_requests == frozenset({4})
        assert spec.poison_requests == frozenset({7})
        assert spec.sigterm_after == 9
        assert spec.active
        assert ChaosSpec.parse(spec.render()) == spec


# --------------------------------------------------------- server: paths


class TestFlowServerPaths:
    def test_ok_response_and_native_unpad(self):
        with _server() as srv:
            img = _img(1, hw=(22, 30))  # needs padding to /8
            r = srv.submit(img, img).result(10)
        assert r.status == STATUS_OK
        assert r.flow.shape == (22, 30, 2)
        assert r.iters == 4 and r.latency_s > 0
        # _DummyModel's flow channel 0 is image1[...,0] * iters: the
        # response must be the NATIVE crop of the padded computation.
        np.testing.assert_allclose(r.flow[..., 0], img[..., 0] * 4,
                                   rtol=1e-6)

    def test_malformed_rejected_at_admission(self):
        with _server() as srv:
            cases = [
                np.zeros((24, 32), np.float32),  # not HWC
                np.zeros((24, 32, 4), np.float32),  # not 3-channel
                np.zeros((4, 4, 3), np.float32),  # below minimum
                np.zeros((24, 32, 3), "U5"),  # non-numeric dtype
            ]
            good = _img()
            out = [srv.submit(bad, good).result(5) for bad in cases]
            mixed = srv.submit(good, _img(2, hw=(40, 48))).result(5)
        assert all(r.status == STATUS_REJECTED for r in out)
        assert mixed.status == STATUS_REJECTED
        assert "differ" in mixed.detail
        assert srv.stats.rejected == 5
        # Malformed requests never occupied queue capacity, and an
        # admission-time validation reject is NOT a quarantine — that
        # list means "poison isolated from live batch-mates".
        assert srv.stats.accepted == 0
        assert srv.stats.quarantined == []

    def test_shed_with_retry_after(self):
        srv = _server(queue_capacity=4)
        try:
            srv.pause()
            img = _img()
            handles = [srv.submit(img, img) for _ in range(7)]
            # Sheds terminate synchronously at submit, before dispatch.
            early = [h.result(0.5) for h in handles if h.done()]
            assert [r.status for r in early] == [STATUS_SHED] * 3
            assert all(r.retry_after_s > 0 for r in early)
            srv.resume()
            responses = [h.result(10) for h in handles]
        finally:
            stats = srv.drain()
        assert stats.shed == 3 and stats.completed == 4
        assert [r.status for r in responses].count(STATUS_OK) == 4

    def test_deadline_expires_in_queue_without_compute(self):
        srv = _server()
        try:
            srv.pause()
            img = _img()
            h_dead = srv.submit(img, img, deadline_s=0.0)
            h_live = srv.submit(img, img)  # no deadline
            time.sleep(0.05)
            srv.resume()
            r_dead, r_live = h_dead.result(10), h_live.result(10)
        finally:
            srv.drain()
        assert r_dead.status == STATUS_TIMEOUT
        assert r_live.status == STATUS_OK
        assert srv.stats.timeouts == 1
        # The expired request consumed zero device compute: only the
        # live one formed a batch.
        assert srv.stats.batches == 1

    def test_batch_padding_accounting(self):
        """3 same-shape requests with batch_sizes (1, 2): one full batch
        of 2, one single — zero-row padding only when a batch lands
        between allowed sizes."""
        srv = _server(batch_sizes=(2, 4))
        try:
            srv.pause()
            img = _img()
            hs = [srv.submit(img, img) for _ in range(3)]
            srv.resume()
            rs = [h.result(10) for h in hs]
        finally:
            srv.drain()
        assert [r.status for r in rs] == [STATUS_OK] * 3
        assert srv.stats.padded_rows >= 1  # the odd request rode a
        # zero-padded program from the fixed set


class TestPoisonIsolation:
    def test_poison_quarantined_batch_mates_unaffected(self):
        """The acceptance contract: a NaN request popped INTO a batch is
        rejected alone; its batch-mates' flow is exactly what the same
        executable returns for them without the poison present."""
        srv = _server(batch_sizes=(1, 2, 4))
        try:
            srv.pause()
            g1, g2 = _img(11), _img(12)
            poison = np.full(g1.shape, np.nan, np.float32)
            h1 = srv.submit(g1, g1)
            hp = srv.submit(poison, poison)
            h2 = srv.submit(g2, g2)
            srv.resume()
            r1, rp, r2 = h1.result(10), hp.result(10), h2.result(10)
        finally:
            srv.drain()
        assert rp.status == STATUS_REJECTED
        assert "non-finite" in rp.detail
        assert srv.stats.quarantined == [hp.result(1).request_id]
        assert r1.status == STATUS_OK and r2.status == STATUS_OK
        np.testing.assert_allclose(r1.flow[..., 0], g1[..., 0] * 4,
                                   rtol=1e-6)
        np.testing.assert_allclose(r2.flow[..., 0], g2[..., 0] * 4,
                                   rtol=1e-6)


class TestServerErrorPath:
    def test_forward_failure_is_error_status_and_server_survives(self):
        """An internal failure terminates the batch's requests with an
        explicit `error` (the fault is the server's, not the client's)
        and the dispatcher keeps serving later batches."""

        class FlakyModel:
            fail = True

            def apply(self, variables, image1, image2, iters=1,
                      flow_init=None, test_mode=True, mesh=None,
                      metric_head=None, **kw):
                if self.fail:
                    raise ValueError("boom")
                flow = jnp.stack([image1[..., 0], image1[..., 1]], axis=-1)
                return image1.mean(), flow

        model = FlakyModel()
        srv = FlowServer(model, {}, _cfg())
        try:
            img = _img()
            r1 = srv.submit(img, img).result(10)
            assert r1.status == "error" and "boom" in r1.detail
            model.fail = False
            assert srv.submit(img, img).result(10).status == STATUS_OK
        finally:
            stats = srv.drain()
        assert stats.errors == 1 and stats.completed == 1


class TestDrainWorkerFailure:
    def test_stranded_batch_flushed_with_correct_attribution(self):
        """AsyncDrain surfaces a worker error from a LATER submit; the
        in-flight registry must complete the batch the worker actually
        stranded (with a drain-failure detail) instead of leaving its
        clients hanging and blaming only the next batch."""

        class AsyncDeadDrainer:
            calls = 0

            def submit(self, tree, cb):
                self.calls += 1
                if self.calls == 1:
                    return  # accepted; worker dies before delivering
                raise RuntimeError("pull failed")

            def close(self):
                pass

        srv = _server(batch_sizes=(1,))
        srv._drainer = AsyncDeadDrainer()
        try:
            img = _img()
            ha = srv.submit(img, img)  # batch 1: stranded by the worker
            hb = srv.submit(img, img)  # batch 2: submit raises
            ra, rb = ha.result(10), hb.result(10)
        finally:
            srv.drain()
        assert ra.status == "error" and "result drain failed" in ra.detail
        assert rb.status == "error"
        assert srv.stats.errors == 2
        assert srv._handles == {} and srv._inflight == {}


class TestNearestRank:
    def test_nearest_rank_percentiles(self):
        from raft_ncup_tpu.serving import nearest_rank_ms

        lat = [i / 1000.0 for i in range(1, 17)]  # 1..16 ms
        # p50 of 16 samples is the 8th smallest (ceil(0.5*16)-1 = idx 7),
        # not the floor-index 9th.
        assert nearest_rank_ms(lat, 0.50) == 8.0
        assert nearest_rank_ms(lat, 0.99) == 16.0
        assert nearest_rank_ms(list(reversed(lat)), 0.50) == 8.0  # sorts
        assert nearest_rank_ms([0.005], 0.50) == 5.0
        assert nearest_rank_ms([], 0.50) is None


class TestBudgetEndToEnd:
    def test_burst_degrades_and_recovers_with_hysteresis(self):
        """Saturate the queue, then let it drain request by request:
        the budget must drop immediately and recover only after the
        patience window — the controller's unit trajectory, reproduced
        through the real dispatcher."""
        srv = _server(queue_capacity=8, batch_sizes=(1,),
                      iter_levels=(4, 2), recover_patience=2)
        try:
            srv.pause()
            img = _img()
            handles = [srv.submit(img, img) for _ in range(8)]
            srv.resume()
            iters_seq = [h.result(20).iters for h in handles]
        finally:
            srv.drain()
        # Depth at assembly walks 8,7,...,1 (submissions finished before
        # resume; max_batch=1): drop at occupancy 1.0, floor through the
        # mid band, recover at the second calm decision.
        assert iters_seq == [2, 2, 2, 2, 2, 2, 2, 4]
        assert srv.budget.drops == 1
        assert srv.budget.recoveries == 1

    def test_burst_chaos_sheds_explicitly_not_unboundedly(self):
        """burst@0 with burst_size > capacity: overflow is shed with a
        retry hint; everything admitted completes. No request is
        silently dropped — submitted == terminal responses."""
        srv = _server(queue_capacity=4, batch_sizes=(1, 2))
        try:
            srv.pause()
            chaos = ChaosSpec.parse("burst@0")
            traffic = SyntheticTraffic((24, 32), 1, seed=5, burst_size=7,
                                       chaos=chaos)
            handles, interrupted = replay(srv, traffic)
            srv.resume()
            responses = [h.result(20) for h in handles]
        finally:
            srv.drain()
        assert not interrupted
        assert len(responses) == 7
        # The no-silent-drop protocol: every handle resolves to one of
        # the five explicit terminal statuses.
        assert all(r.status in TERMINAL_STATUSES for r in responses)
        by_status = {}
        for r in responses:
            by_status.setdefault(r.status, []).append(r)
        assert len(by_status[STATUS_SHED]) == 3  # 7 - capacity 4
        assert len(by_status[STATUS_OK]) == 4
        assert all(r.retry_after_s is not None
                   for r in by_status[STATUS_SHED])
        assert srv.stats.submitted == 7
        assert srv.stats.shed == 3 and srv.stats.completed == 4


class TestGracefulDrain:
    def test_sigterm_mid_flight_drains_all_admitted(self):
        """The drain contract through the REAL signal machinery: a
        SIGTERM delivered mid-stream stops submissions at once, every
        admitted request is flushed through compute, nothing hangs."""
        srv = _server(queue_capacity=16)
        with PreemptionHandler() as preempt:
            traffic = SyntheticTraffic((24, 32), 12, seed=7)
            chaos = ChaosSpec.parse("sigterm@5")
            handles, interrupted = replay(
                srv, traffic, preempt=preempt,
                sigterm_after=chaos.sigterm_after,
            )
            stats = srv.drain(timeout=30)
        assert interrupted
        assert len(handles) == 5  # submissions stopped at the signal
        responses = [h.result(10) for h in handles]
        assert [r.status for r in responses] == [STATUS_OK] * 5
        assert stats.accepted == stats.completed == 5
        assert not srv._thread.is_alive()
        assert srv._handles == {}  # nothing admitted was dropped

    def test_drain_sheds_new_submissions_flushes_old(self):
        srv = _server()
        srv.pause()
        img = _img()
        admitted = [srv.submit(img, img) for _ in range(3)]
        drainer = threading.Thread(target=srv.drain)
        drainer.start()
        time.sleep(0.05)
        refused = srv.submit(img, img)
        srv.resume()
        drainer.join(timeout=30)
        assert not drainer.is_alive()
        assert [h.result(10).status for h in admitted] == [STATUS_OK] * 3
        r = refused.result(5)
        assert r.status == STATUS_SHED and r.detail == "draining"

    def test_drain_idempotent(self):
        srv = _server()
        img = _img()
        h = srv.submit(img, img)
        assert h.result(10).ok
        s1 = srv.drain()
        s2 = srv.drain()
        assert s1 is s2


# ---------------------------------------------- real model + invariants


@pytest.fixture(scope="module")
def tiny_model():
    cfg = small_model_config("raft", dataset="chairs")
    model = RAFT(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, 40, 48, 3))
    return model, variables


class TestRealModelServing:
    def test_response_matches_direct_forward_bitwise(self, tiny_model):
        """A served request's flow equals the same executable invoked
        directly on the identically staged batch — serving adds routing,
        never numerics."""
        from raft_ncup_tpu.inference.pipeline import ShapeCachedForward

        model, variables = tiny_model
        cfg = _cfg(batch_sizes=(1,), iter_levels=(2, 1))
        img1, img2 = _img(21, (40, 48)), _img(22, (40, 48))
        with FlowServer(model, variables, cfg) as srv:
            r = srv.submit(img1, img2).result(120)
        assert r.status == STATUS_OK and r.iters == 2
        ref_fwd = ShapeCachedForward(model, variables)
        _, ref = ref_fwd(img1[None], img2[None], 2)
        np.testing.assert_array_equal(r.flow, ref[0])

    def test_steady_state_sync_free_recompile_free(
        self, tiny_model, forbid_host_transfers, max_recompiles
    ):
        """The serving invariant the bench row records: once warmup has
        compiled the executable set, a steady window performs ZERO
        implicit host pulls and ZERO compiles — each batch's single
        result pull rides the sanctioned explicit device_get in the
        AsyncDrain worker."""
        model, variables = tiny_model
        cfg = _cfg(batch_sizes=(1,), iter_levels=(2, 1))
        srv = FlowServer(model, variables, cfg)
        try:
            srv.warmup((40, 48))
            warm = srv.submit(_img(30, (40, 48)), _img(31, (40, 48)))
            assert warm.result(120).ok
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
        # One sanctioned pull per batch: the product path.
        assert stats.sanctioned_gets == 3

    def test_pad_bucket_collapses_shapes_into_one_program(self, tiny_model):
        """Two native shapes inside one bucket share a padded shape —
        they batch together and compile ONE executable (the bounded
        executable-set contract under mixed-resolution traffic)."""
        model, variables = tiny_model
        cfg = _cfg(batch_sizes=(1, 2), iter_levels=(2,), pad_bucket=48)
        srv = FlowServer(model, variables, cfg)
        try:
            srv.pause()
            ha = srv.submit(_img(61, (37, 45)), _img(62, (37, 45)))
            hb = srv.submit(_img(63, (40, 48)), _img(64, (40, 48)))
            srv.resume()
            ra, rb = ha.result(120), hb.result(120)
        finally:
            srv.drain()
        assert ra.status == STATUS_OK and rb.status == STATUS_OK
        assert ra.flow.shape == (37, 45, 2)
        assert rb.flow.shape == (40, 48, 2)
        assert srv.stats.batches == 1  # same bucket -> one micro-batch
        assert srv._fwd.stats["compiles"] == 1


class TestUhdAdmission:
    """4K requests are admissible by default (docs/PERF.md "Banded
    dispatch"): the ServeConfig ceiling is UHD 2176x3840 — the banded
    corr tier broke the memory wall that justified the old 1088x1920
    rejection — while oversized frames still reject crisply."""

    def test_default_ceiling_is_uhd(self):
        assert ServeConfig().max_image_hw == (2176, 3840)

    def test_4k_passes_admission_validation(self):
        server = _server()
        try:
            fake = SimpleNamespace(
                shape=(2176, 3840, 3), dtype=np.float32
            )
            assert server._admission_error(fake) is None
            too_big = SimpleNamespace(
                shape=(2184, 3840, 3), dtype=np.float32
            )
            err = server._admission_error(too_big)
            assert err is not None and "exceeds maximum" in err
        finally:
            server.drain()


# ------------------------------------------- stage spans of one batch


_BATCH_STAGES = (
    "serve_dispatch", "serve_throttle_wait", "serve_device_wait",
    "serve_pull", "serve_deliver",
)


class _TickClock:
    """Every read is one second after the last, from any thread."""

    def __init__(self):
        self._t, self._lock = 0.0, threading.Lock()

    def __call__(self) -> float:
        with self._lock:
            self._t += 1.0
            return self._t


def _stage_records(tel, batch_id):
    out = {}
    for name in _BATCH_STAGES + ("serve_drain",):
        (rec,) = [
            r for r in tel.tracer.records(name)
            if r["attrs"]["batch_id"] == batch_id
        ]
        out[name] = (rec["t_s"], rec["t_s"] + rec["duration_ms"] / 1e3)
    return out


class TestBatchStageSpans:
    """``serve_dispatch`` is the copy and the jit dispatch alone; the
    throttle's wait and the drain worker's device wait, pull and deliver
    are spans of their own under the batch's id. With the wait in the
    drainer's queue they tile the externally timed ``serve_drain``."""

    def test_stages_tile_serve_drain_under_an_injected_clock(self):
        from raft_ncup_tpu.observability import Telemetry

        clock = _TickClock()
        tel = Telemetry(clock=clock)
        srv = FlowServer(
            _DummyModel(), {}, _cfg(), clock=clock, telemetry=tel
        )
        try:
            srv.pause()
            h1 = srv.submit(_img(1), _img(2))
            h2 = srv.submit(_img(3), _img(4))
            srv.resume()
            assert h1.result(60).ok and h2.result(60).ok
            _wait_idle(srv)
        finally:
            srv.drain()
        st = _stage_records(tel, batch_id=0)
        order = [st[name] for name in _BATCH_STAGES]
        # disjoint, in this order, each one tick long
        for (s0, e0), (s1, e1) in zip(order, order[1:]):
            assert s0 < e0 < s1 < e1
        # serve_drain runs from the read before serve_dispatch to the
        # read at the top of deliver; observe_ms reads the clock once
        # more to place it, hence the tick taken off its end.
        drain_start, drain_end = st["serve_drain"][0] - 1.0, st["serve_drain"][1] - 1.0
        assert drain_start == st["serve_dispatch"][0] - 1.0
        assert st["serve_pull"][1] < drain_end
        assert st["serve_deliver"][0] < drain_end < st["serve_deliver"][1]
        covered = sum(e - s for s, e in order[:4])
        # what the four spans leave of serve_drain is clock reads alone:
        # 2 per span boundary pair, plus deliver's start and `done`.
        assert (drain_end - drain_start) - covered == pytest.approx(6.0)

    def test_every_batch_has_each_stage_once_inside_its_drain(self):
        from raft_ncup_tpu.observability import Telemetry

        tel = Telemetry()
        srv = FlowServer(_DummyModel(), {}, _cfg(), telemetry=tel)
        try:
            handles = [srv.submit(_img(i), _img(i + 1)) for i in range(5)]
            assert all(h.result(60).ok for h in handles)
            _wait_idle(srv)
        finally:
            stats = srv.drain()
        batches = {r["attrs"]["batch_id"] for r in tel.tracer.records("serve_drain")}
        assert len(batches) == stats.batches >= 3
        for b in batches:
            st = _stage_records(tel, b)  # exactly one record a stage
            order = [st[name] for name in _BATCH_STAGES]
            slack = 2e-3  # records round to microseconds and 1e-3 ms
            for (s0, e0), (s1, e1) in zip(order, order[1:]):
                assert s0 <= e0 <= s1 + slack and s1 <= e1
            d0, d1 = st["serve_drain"]
            assert d0 - slack <= order[0][0] and order[3][1] <= d1 + slack
        report = srv.report()["stages"]
        assert {n for n in _BATCH_STAGES} <= set(report)
        assert report["serve_pull"]["count"] == stats.batches
