"""Async inference subsystem (raft_ncup_tpu/inference/): pipeline
contracts (order, exceptions, clean close), the bounded shape cache, the
device-resident metric parity against the pre-refactor host NumPy
formulas, and the eval loop's sync-free/recompile-free invariants under
the runtime guards.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import DataConfig, small_model_config
from raft_ncup_tpu.data.synthetic import SyntheticFlowDataset
from raft_ncup_tpu.inference import metrics as metrics_mod
from raft_ncup_tpu.inference.pipeline import (
    AsyncDrain,
    DispatchThrottle,
    EvalPipeline,
    SamplePrefetcher,
    ShapeCachedForward,
    uniform_batches,
)
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.ops import InputPadder


# ------------------------------------------------------------- test rigs


class _ListDataset:
    """Minimal dataset protocol over a list of sample dicts."""

    def __init__(self, samples):
        self._samples = samples

    def __len__(self):
        return len(self._samples)

    def sample(self, index):
        return self._samples[index]


class _FailingDataset(_ListDataset):
    def __init__(self, samples, fail_at: int):
        super().__init__(samples)
        self._fail_at = fail_at

    def sample(self, index):
        if index == self._fail_at:
            raise ValueError(f"decode failed at {index}")
        return super().sample(index)


class _DummyModel:
    """apply()-compatible stand-in whose jitted programs compile
    instantly — exercises the cache/LRU machinery without RAFT compiles."""

    def apply(self, variables, image1, image2, iters=1, flow_init=None,
              test_mode=True, mesh=None, metric_head=None, **kw):
        flow_up = jnp.stack([image1[..., 0], image1[..., 1]], axis=-1)
        if metric_head is not None:
            return image1.mean(), metric_head(flow_up)
        return image1.mean(), flow_up


def _mk_samples(n, hw=(8, 10)):
    g = np.random.default_rng(3)
    return [
        {
            "image1": g.random((*hw, 3), np.float32),
            "image2": g.random((*hw, 3), np.float32),
            "flow": g.random((*hw, 2), np.float32),
        }
        for _ in range(n)
    ]


# ----------------------------------------------------- SamplePrefetcher


class TestSamplePrefetcher:
    def test_order_and_contents(self):
        samples = _mk_samples(7)
        with SamplePrefetcher(_ListDataset(samples), num_workers=3,
                              lookahead=2) as sp:
            got = list(sp)
        assert len(got) == 7
        for a, b in zip(got, samples):
            np.testing.assert_array_equal(a["image1"], b["image1"])

    def test_exception_propagates_and_pool_closes(self):
        sp = SamplePrefetcher(
            _FailingDataset(_mk_samples(6), fail_at=3), num_workers=2
        )
        got = []
        with pytest.raises(ValueError, match="decode failed at 3"):
            for s in sp:
                got.append(s)
        assert len(got) == 3
        assert sp._pool._shutdown  # pool joined, no leaked threads

    def test_early_exit_closes_pool(self):
        """The old _prefetch_samples generator, abandoned mid-validation,
        left its pool threads parked forever; the context manager (and
        close()) must tear them down."""
        sp = SamplePrefetcher(_ListDataset(_mk_samples(16)), num_workers=2)
        next(iter(sp))
        sp.close()
        assert sp._pool._shutdown
        sp.close()  # idempotent

    def test_exhaustion_closes_pool(self):
        sp = SamplePrefetcher(_ListDataset(_mk_samples(3)), num_workers=2)
        list(sp)
        assert sp._pool._shutdown


# ------------------------------------------------------ uniform_batches


class TestUniformBatches:
    def test_groups_by_size_across_the_stream(self):
        a = {"image1": np.zeros((4, 6, 3), np.float32)}
        b = {"image1": np.zeros((6, 4, 3), np.float32)}
        groups = list(uniform_batches(iter([a, a, a, b, b, a]), 2))
        # no short group at a change of shape: a's four make two full groups
        assert [(len(g), g[0]["image1"].shape[0]) for g in groups] == [
            (2, 4), (2, 6), (2, 4)]

    @pytest.mark.parametrize("n,batch_size,sizes", [
        (5, 2, [2, 2, 1]), (4, 4, [4]), (3, 1, [1, 1, 1]), (7, 3, [3, 3, 1]),
    ])
    def test_one_size_comes_out_as_it_always_did(self, n, batch_size, sizes):
        """A stream of one size (Sintel, chairs): full groups in arrival
        order and one short last group, with or without ``fill_valid`` off."""
        samples = _mk_samples(n)
        groups = list(uniform_batches(iter(samples), batch_size))
        assert [len(g) for g in groups] == sizes
        assert [s for g in groups for s in g] == samples  # order kept


# ---------------------------------- a pass over mixed native sizes (KITTI)

MIXED_COUNTS = {(13, 21): 5, (11, 18): 3, (16, 24): 6}  # all pad to 16x24
MIXED_ORDERS = ["runs", 0, 1, 2, 3]


def _mixed_samples(order, counts=MIXED_COUNTS):
    """Samples of three native sizes with sparse masks, in runs by size or
    in a seeded permutation; the same samples whatever the order."""
    g = np.random.default_rng(7)
    samples = []
    for hw, n in counts.items():
        for _ in range(n):
            valid = (g.random(hw) < 0.3).astype(np.float32)
            valid[: hw[0] // 3] = 0.0
            samples.append({
                "image1": g.random((*hw, 3), np.float32) * 4,
                "image2": g.random((*hw, 3), np.float32),
                "flow": g.random((*hw, 2), np.float32) * 4,
                "valid": valid,
            })
    if order != "runs":
        samples = [samples[i] for i in np.random.default_rng(order).permutation(len(samples))]
    return samples


class TestMixedSizePass:
    """``uniform_batches(fill_valid=True)`` and the pass built on it: one
    executable a native size whatever the order of arrival, remainders
    dispatched as whole batches whose fill rows count as no frame, and the
    sums of the one-pair-at-a-time pass."""

    BATCH = 4

    def _pass(self, fwd, samples, batch_size, tel=None):
        from raft_ncup_tpu.evaluation import _run_metric_pass

        return _run_metric_pass(
            fwd, _ListDataset(samples), kind="kitti", iters=1,
            batch_size=batch_size, pad_mode="kitti", with_valid=True,
            num_workers=2, telemetry=tel,
        )

    @pytest.mark.parametrize("order", MIXED_ORDERS)
    def test_same_groups_for_every_order_and_pending_is_bounded(self, order):
        samples = _mixed_samples(order)
        consumed = [0]

        def stream():
            for s in samples:
                consumed[0] += 1
                yield s

        groups, real_out = [], 0
        for g in uniform_batches(stream(), self.BATCH, fill_valid=True):
            if consumed[0] < len(samples):  # mid-stream: what is still pending
                real_out += len(g)
                pending = consumed[0] - real_out
                assert pending <= len(MIXED_COUNTS) * (self.BATCH - 1)
            groups.append(g)
        assert all(len(g) == self.BATCH for g in groups)
        assert all(len({s["image1"].shape for s in g}) == 1 for g in groups)
        shape_of = sorted(
            (g[0]["image1"].shape[:2], sum(1 for s in g if s.get("fill"))) for g in groups)
        assert shape_of == [((11, 18), 1), ((13, 21), 0), ((13, 21), 3),
                            ((16, 24), 0), ((16, 24), 2)]
        real = [s for g in groups for s in g if not s.get("fill")]
        assert sorted(id(s) for s in real) == sorted(id(s) for s in samples)
        for g in groups:
            for s in g:
                if s.get("fill"):  # a real sample of the group again, masked out
                    assert not s["valid"].any()
                    assert any(s["image1"] is r["image1"] for r in g if not r.get("fill"))

    @pytest.mark.parametrize("order", MIXED_ORDERS)
    def test_one_program_a_size_and_the_sums_of_one_pair_at_a_time(self, order):
        from raft_ncup_tpu.observability import Telemetry

        samples = _mixed_samples(order)
        tel = Telemetry()
        fwd = ShapeCachedForward(_DummyModel(), {}, telemetry=tel)
        accs = [self._pass(fwd, samples, self.BATCH, tel) for _ in range(3)]
        assert fwd.stats["compiles"] == 3 and fwd.stats["evictions"] == 0
        events = tel.tracer.records("eval_pass_programs")
        assert [(e["attrs"]["compiles"], e["attrs"]["evictions"]) for e in events] == [
            (3, 0), (0, 0), (0, 0)]
        assert tel.registry.get("eval_programs_resident").value == 3
        # 14 real pairs in 5 batches of 4: 6 fill rows, counted as rows, not as pairs
        assert tel.counter_value("eval_pairs_total") == 3 * 14
        assert tel.counter_value("eval_rows_total") == 3 * 20
        assert tel.counter_value("eval_fill_rows_total") == 3 * 6
        sizes = {r["attrs"]["size"] for r in tel.tracer.records("eval_dispatch")}
        assert sizes == {"13x21", "11x18", "16x24"}
        # upstream's way: one pair at a time, no fill rows
        one = self._pass(ShapeCachedForward(_DummyModel(), {}, telemetry=Telemetry()), samples, 1)
        for acc in accs:
            assert acc[1] == one[1] == 14  # frames: a fill row is no frame
            np.testing.assert_array_equal(acc[2:], one[2:])  # outliers, valid pixels
            np.testing.assert_allclose(acc[0], one[0], rtol=1e-5)  # float32 summation order

    def test_a_real_frame_without_a_valid_pixel_still_counts_as_no_frame(self):
        samples = _mixed_samples(1)
        samples[4] = {**samples[4], "valid": np.zeros_like(samples[4]["valid"])}
        from raft_ncup_tpu.observability import Telemetry

        tel = Telemetry()
        acc = self._pass(ShapeCachedForward(_DummyModel(), {}, telemetry=tel), samples, self.BATCH, tel)
        assert acc[1] == 13 and tel.counter_value("eval_pairs_total") == 14
        assert acc[3] == sum(s["valid"].sum() for s in samples)

    def test_a_cache_smaller_than_the_sizes_is_reported_pass_by_pass(self, capsys):
        from raft_ncup_tpu.observability import Telemetry

        tel = Telemetry()
        fwd = ShapeCachedForward(_DummyModel(), {}, cache_size=2, telemetry=tel)
        for _ in range(2):
            self._pass(fwd, _mixed_samples(2), self.BATCH, tel)
        events = [e["attrs"] for e in tel.tracer.records("eval_pass_programs")]
        assert events[1]["compiles"] > 0 and events[1]["evictions"] > 0
        assert tel.registry.get("eval_programs_resident").value == 2
        assert "EVICTING" in capsys.readouterr().err

    @pytest.mark.parametrize("kind,extra", [("epe", {}), ("px", {"pad_mode": "sintel"})])
    def test_kinds_without_a_mask_keep_their_short_last_batch(self, kind, extra):
        """No fill rows where the batch has no ``valid``: the remainder of
        each size is dispatched short, as a Sintel pass's always was, so those
        kinds keep the executables (and keys) they had."""
        from raft_ncup_tpu.evaluation import _run_metric_pass
        from raft_ncup_tpu.observability import Telemetry

        tel = Telemetry()
        fwd = ShapeCachedForward(_DummyModel(), {}, telemetry=tel)
        samples = _mk_samples(5, hw=(16, 24))
        acc = _run_metric_pass(
            fwd, _ListDataset(samples), kind=kind, iters=1, batch_size=2,
            num_workers=2, telemetry=tel, **extra,
        )
        assert acc[1] == 5 * 16 * 24
        assert tel.counter_value("eval_fill_rows_total") == 0
        assert tel.counter_value("eval_rows_total") == tel.counter_value("eval_pairs_total") == 5
        batches = sorted(key[2][0] for key in fwd._fns)  # the keys' image shapes
        assert batches == [1, 2] and all("valid" not in key[4] for key in fwd._fns)


# --------------------------------------------------------- EvalPipeline


class TestEvalPipeline:
    @staticmethod
    def _stage(group):
        return (
            {"image1": np.stack([s["image1"] for s in group])},
            {"n": len(group)},
        )

    def test_yields_device_batches_with_aligned_meta(self):
        samples = _mk_samples(5)
        with EvalPipeline(
            _ListDataset(samples), self._stage, batch_size=2
        ) as pipe:
            out = list(pipe)
        assert [m["n"] for _, m in out] == [2, 2, 1]
        assert all(isinstance(b["image1"], jax.Array) for b, _ in out)
        np.testing.assert_allclose(
            np.asarray(out[0][0]["image1"][1]), samples[1]["image1"],
            rtol=1e-6,
        )

    def test_stage_exception_propagates(self):
        def bad_stage(group):
            raise RuntimeError("stage blew up")

        with pytest.raises(RuntimeError, match="stage blew up"):
            with EvalPipeline(
                _ListDataset(_mk_samples(4)), bad_stage, batch_size=2
            ) as pipe:
                list(pipe)

    def test_decode_exception_propagates(self):
        with pytest.raises(ValueError, match="decode failed"):
            with EvalPipeline(
                _FailingDataset(_mk_samples(6), fail_at=2),
                self._stage,
                batch_size=2,
            ) as pipe:
                list(pipe)

    def test_close_mid_epoch_leaks_no_threads(self):
        pipe = EvalPipeline(
            _ListDataset(_mk_samples(32)), self._stage, batch_size=2
        )
        next(iter(pipe))
        pipe.close()
        deadline = time.time() + 5.0
        while pipe._pf._thread.is_alive() and time.time() < deadline:
            time.sleep(0.01)
        assert not pipe._pf._thread.is_alive()
        assert pipe._sp._pool._shutdown


# ----------------------------------------------------------- AsyncDrain


class TestAsyncDrain:
    def test_order_preserving_callbacks(self):
        got = []
        with AsyncDrain(depth=2) as drain:
            for i in range(6):
                drain.submit(
                    jnp.full((3,), i),
                    lambda host, i=i: got.append((i, float(host[0]))),
                )
        assert got == [(i, float(i)) for i in range(6)]

    def test_callback_error_reraises(self):
        drain = AsyncDrain(depth=1)

        def boom(host):
            raise RuntimeError("writer failed")

        drain.submit(jnp.zeros(()), boom)
        with pytest.raises(RuntimeError, match="writer failed"):
            for _ in range(50):
                drain.submit(jnp.zeros(()), lambda host: None)
                time.sleep(0.01)
            drain.close()

    def test_close_flushes_pending(self):
        got = []
        drain = AsyncDrain(depth=4)
        for i in range(4):
            drain.submit(jnp.full((1,), i), lambda h, i=i: got.append(i))
        drain.close()
        assert got == [0, 1, 2, 3]
        assert not drain._thread.is_alive()


# ----------------------------------------------------- DispatchThrottle


class TestDispatchThrottle:
    def test_bounds_pending_and_drains(self):
        th = DispatchThrottle(inflight=2)
        xs = [jnp.full((2,), i) for i in range(5)]
        for x in xs:
            th.push(x)
            assert len(th._pending) <= 1  # <= inflight - 1 after push
        th.drain()
        assert not th._pending

    def test_serial_mode_keeps_nothing_pending(self):
        th = DispatchThrottle(inflight=1)
        th.push(jnp.zeros((2,)))
        assert not th._pending


# ------------------------------------------------- ShapeCachedForward LRU


class TestShapeCacheLRU:
    def _fwd(self, cache_size):
        return ShapeCachedForward(
            _DummyModel(), {"params": {}}, cache_size=cache_size
        )

    def _img(self, h, w):
        return np.zeros((1, h, w, 3), np.float32)

    def test_bounded_lru_evicts_and_counts(self, capsys):
        fwd = self._fwd(cache_size=2)
        fwd.forward_device(self._img(8, 8), self._img(8, 8), iters=1)
        fwd.forward_device(self._img(8, 16), self._img(8, 16), iters=1)
        assert fwd.stats == {"compiles": 2, "hits": 0, "evictions": 0}
        # Third shape evicts the least-recently-used first shape, loudly.
        fwd.forward_device(self._img(16, 8), self._img(16, 8), iters=1)
        assert fwd.stats["evictions"] == 1
        assert "EVICTING compiled executable" in capsys.readouterr().err
        # The evicted shape recompiles; the resident one hits.
        fwd.forward_device(self._img(8, 16), self._img(8, 16), iters=1)
        assert fwd.stats["hits"] == 1
        fwd.forward_device(self._img(8, 8), self._img(8, 8), iters=1)
        assert fwd.stats["compiles"] == 4
        assert fwd.stats["evictions"] == 2

    def test_lru_recency_order(self):
        fwd = self._fwd(cache_size=2)
        fwd.forward_device(self._img(8, 8), self._img(8, 8), iters=1)
        fwd.forward_device(self._img(8, 16), self._img(8, 16), iters=1)
        # Touch the first entry so the SECOND is now least-recent...
        fwd.forward_device(self._img(8, 8), self._img(8, 8), iters=1)
        fwd.forward_device(self._img(16, 8), self._img(16, 8), iters=1)
        # ...and the first survives the eviction.
        fwd.forward_device(self._img(8, 8), self._img(8, 8), iters=1)
        assert fwd.stats["hits"] == 2
        assert fwd.stats["compiles"] == 3

    def test_pad_bucketing_collapses_executables(self):
        """Two KITTI-ish native shapes bucket to ONE padded shape → one
        compiled executable on the forward path (the submission loop)."""
        fwd = self._fwd(cache_size=8)
        for h, w in ((37, 41), (38, 44)):
            img = np.zeros((1, h, w, 3), np.float32)
            padder = InputPadder(img.shape, mode="kitti", bucket=48)
            p1, p2 = padder.pad(img, img)
            assert np.asarray(p1).shape[1:3] == (48, 48)
            fwd.forward_device(np.asarray(p1), np.asarray(p2), iters=1)
        assert fwd.stats == {"compiles": 1, "hits": 1, "evictions": 0}

    def test_bad_bucket_rejected(self):
        with pytest.raises(ValueError, match="multiple of"):
            InputPadder((1, 37, 41, 3), bucket=12)  # not divisible by 8


# ------------------------------------------- device-metric parity + guards


def _epe_band_dataset(n, hw):
    return SyntheticFlowDataset(hw, length=n, seed=11, style="smooth")


class _MaskedValid(_ListDataset):
    """Synthetic samples with a nontrivial valid mask (upper half of
    every even frame invalid) so the KITTI fold's masking is exercised."""

    def __init__(self, base):
        samples = []
        for i in range(len(base)):
            s = dict(base.sample(i))
            valid = np.ones(s["flow"].shape[:2], np.float32)
            if i % 2 == 0:
                valid[: valid.shape[0] // 2] = 0.0
            s["valid"] = valid
            samples.append(s)
        super().__init__(samples)


@pytest.fixture(scope="module", params=["volume", "onthefly"])
def tiny_fwd(request):
    cfg = small_model_config(
        "raft", dataset="chairs", corr_impl=request.param
    )
    model = RAFT(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, 40, 48, 3))
    return ShapeCachedForward(model, variables)


class TestDeviceMetricParity:
    """The acceptance contract: validators' on-device sums reproduce the
    pre-refactor host-side NumPy computation (reference formulas:
    evaluate.py:90-182) for both corr implementations."""

    ITERS = 2

    def _run_device(self, fwd, dataset, kind, batch_size=2, pad_mode=None,
                    with_valid=False):
        from raft_ncup_tpu.evaluation import _run_metric_pass

        return _run_metric_pass(
            fwd, dataset, kind=kind, iters=self.ITERS,
            batch_size=batch_size, pad_mode=pad_mode,
            with_valid=with_valid, num_workers=2,
        )

    def _host_flow(self, fwd, group, pad_mode=None):
        """The pre-refactor per-batch path: stack, pad, forward, PULL
        full fields, unpad host-side."""
        img1 = np.stack([s["image1"] for s in group]).astype(np.float32)
        img2 = np.stack([s["image2"] for s in group]).astype(np.float32)
        if pad_mode is None:
            _, flow_up = fwd(img1, img2, self.ITERS)
            return flow_up
        padder = InputPadder(img1.shape, mode=pad_mode)
        p1, p2 = padder.pad(img1, img2)
        _, flow_up = fwd(np.asarray(p1), np.asarray(p2), self.ITERS)
        return np.asarray(padder.unpad(flow_up))

    def test_epe_parity_unpadded(self, tiny_fwd):
        ds = _epe_band_dataset(6, (40, 48))
        acc = self._run_device(tiny_fwd, ds, "epe")
        # Host reference: evaluate.py:90-108 (chairs EPE).
        host = np.zeros(2)
        for g0 in range(0, 6, 2):
            group = [ds.sample(g0 + k) for k in range(2)]
            flow_up = self._host_flow(tiny_fwd, group)
            for k, s in enumerate(group):
                epe = np.sqrt(((flow_up[k] - s["flow"]) ** 2).sum(-1))
                host += (float(epe.sum()), epe.size)
        np.testing.assert_allclose(acc, host, rtol=1e-4)

    def test_px_parity_padded(self, tiny_fwd):
        # Native 36x44 pads to 40x48 (sintel-centered), so the in-graph
        # unpad crop is live in the compiled program.
        ds = _epe_band_dataset(4, (36, 44))
        acc = self._run_device(tiny_fwd, ds, "px", pad_mode="sintel")
        # Host reference: evaluate.py:111-143 (sintel EPE + 1/3/5px).
        host = np.zeros(5)
        for g0 in range(0, 4, 2):
            group = [ds.sample(g0 + k) for k in range(2)]
            flow_b = self._host_flow(tiny_fwd, group, pad_mode="sintel")
            for k, s in enumerate(group):
                epe = np.sqrt(((flow_b[k] - s["flow"]) ** 2).sum(-1))
                host += (
                    float(epe.sum()), epe.size,
                    int((epe < 1).sum()), int((epe < 3).sum()),
                    int((epe < 5).sum()),
                )
        np.testing.assert_allclose(acc[:2], host[:2], rtol=1e-4)
        # Threshold counts are integers: exact equality required.
        np.testing.assert_array_equal(acc[2:], host[2:])

    def test_kitti_parity_padded_masked(self, tiny_fwd):
        ds = _MaskedValid(_epe_band_dataset(4, (36, 44)))
        acc = self._run_device(
            tiny_fwd, ds, "kitti", pad_mode="kitti", with_valid=True
        )
        # Host reference: evaluate.py:146-182 (KITTI EPE + F1 sums).
        host = np.zeros(4)
        for g0 in range(0, 4, 2):
            group = [ds.sample(g0 + k) for k in range(2)]
            flow_b = self._host_flow(tiny_fwd, group, pad_mode="kitti")
            for k, s in enumerate(group):
                epe = np.sqrt(((flow_b[k] - s["flow"]) ** 2).sum(-1)).ravel()
                mag = np.sqrt((s["flow"] ** 2).sum(-1)).ravel()
                val = s["valid"].ravel() >= 0.5
                out = (epe > 3.0) & ((epe / np.maximum(mag, 1e-12)) > 0.05)
                host += (
                    float(epe[val].mean()), 1,
                    int(out[val].sum()), int(val.sum()),
                )
        np.testing.assert_allclose(acc[0], host[0], rtol=1e-4)
        np.testing.assert_array_equal(acc[1:], host[1:])

    def test_finalize_matches_reference_reduction(self):
        acc = np.array([10.0, 4.0, 2.0, 3.0, 4.0])
        m = metrics_mod.finalize("px", acc)
        assert m == {
            "epe": 2.5, "1px": 0.5, "3px": 0.75, "5px": 1.0,
        }
        k = metrics_mod.finalize("kitti", np.array([6.0, 3.0, 5.0, 50.0]))
        assert k == {"epe": 2.0, "f1": 10.0}


class TestKittiEmptyValidMask:
    """ROADMAP carry-over regression: a frame with ZERO valid pixels made
    the host path's per-frame EPE mean NaN (0-valid sum / 0 count) and
    poisoned the dataset mean; with nothing valid pooled at all,
    ``finalize``'s ``acc[2]/acc[3]`` divided 0/0. Empty frames now
    contribute neither EPE nor frame count; degenerate pools finalize to
    0.0, never NaN."""

    def _acc(self, valid: np.ndarray) -> np.ndarray:
        g = np.random.default_rng(5)
        b, h, w = valid.shape
        flow_up = jnp.asarray(g.normal(size=(b, h, w, 2)).astype(np.float32))
        gt = jnp.asarray(g.normal(size=(b, h, w, 2)).astype(np.float32))
        acc = metrics_mod.accumulate(
            "kitti", metrics_mod.init_acc("kitti"), flow_up, gt,
            valid=jnp.asarray(valid),
        )
        self._flow_up, self._gt = np.asarray(flow_up), np.asarray(gt)
        return np.asarray(jax.device_get(acc))

    def test_all_invalid_frame_excluded_not_nan(self):
        valid = np.ones((2, 8, 10), np.float32)
        valid[1] = 0.0  # frame 1: zero valid pixels
        acc = self._acc(valid)
        assert np.isfinite(acc).all()
        # The empty frame contributes neither EPE nor frame count, so
        # the remaining frame's mean is undiluted.
        epe0 = np.sqrt(
            ((self._flow_up[0] - self._gt[0]) ** 2).sum(-1)
        )
        assert acc[1] == 1.0
        np.testing.assert_allclose(acc[0], epe0.mean(), rtol=1e-5)
        m = metrics_mod.finalize("kitti", acc)
        np.testing.assert_allclose(m["epe"], epe0.mean(), rtol=1e-5)
        assert np.isfinite(m["f1"])

    def test_every_frame_invalid_finalizes_to_zero(self):
        acc = self._acc(np.zeros((2, 8, 10), np.float32))
        assert np.isfinite(acc).all() and acc[1] == 0.0
        assert metrics_mod.finalize("kitti", acc) == {"epe": 0.0, "f1": 0.0}


class TestEvalLoopInvariants:
    """N eval batches under forbid_host_transfers + max_recompiles: only
    the sanctioned window pull touches the host, and the warm loop never
    recompiles — the train loop's invariants, inherited by eval."""

    def test_metric_pass_is_sync_free_and_recompile_free(
        self, forbid_host_transfers, max_recompiles
    ):
        cfg = small_model_config("raft", dataset="chairs")
        model = RAFT(cfg)
        variables = model.init(jax.random.PRNGKey(0), (1, 40, 48, 3))
        fwd = ShapeCachedForward(model, variables)
        from raft_ncup_tpu.evaluation import _run_metric_pass

        ds = _epe_band_dataset(6, (40, 48))
        # Warm pass compiles the metric executable + init_acc programs.
        warm = _run_metric_pass(
            fwd, ds, kind="epe", iters=2, batch_size=2, num_workers=2
        )
        with forbid_host_transfers() as stats, max_recompiles(0):
            guarded = _run_metric_pass(
                fwd, ds, kind="epe", iters=2, batch_size=2, num_workers=2
            )
        assert stats.host_transfers == 0
        assert stats.sanctioned_gets == 1  # ONE window pull, nothing else
        np.testing.assert_allclose(guarded, warm, rtol=1e-6)

    def test_validator_outputs_unchanged_by_guards(self):
        """validate_synthetic through the full pipeline equals a direct
        old-style host computation over the same held-out split."""
        from raft_ncup_tpu.evaluation import validate_synthetic

        cfg = small_model_config("raft", dataset="chairs")
        model = RAFT(cfg)
        variables = model.init(jax.random.PRNGKey(0), (1, 40, 48, 3))
        out = validate_synthetic(
            model, variables, DataConfig(), iters=2, batch_size=2,
            size_hw=(40, 48), length=4,
        )
        fwd = ShapeCachedForward(model, variables)
        ds = SyntheticFlowDataset((40, 48), length=4, seed=999,
                                  style="smooth")
        host = np.zeros(2)
        for g0 in range(0, 4, 2):
            group = [ds.sample(g0 + k) for k in range(2)]
            img1 = np.stack([s["image1"] for s in group]).astype(np.float32)
            img2 = np.stack([s["image2"] for s in group]).astype(np.float32)
            _, flow_up = fwd(img1, img2, 2)
            for k, s in enumerate(group):
                epe = np.sqrt(((flow_up[k] - s["flow"]) ** 2).sum(-1))
                host += (float(epe.sum()), epe.size)
        np.testing.assert_allclose(
            out["synthetic"], host[0] / host[1], rtol=1e-4
        )


# ----------------------------------------------------------- cost ledger


class TestCostLedger:
    """The executable cost ledger (inference/costs.py; docs/PERF.md):
    XLA cost facts recorded once at compile time, keys stable across
    re-warm, MFU non-null for any backend with a peak-FLOPs entry."""

    def _fwd_with_ledger(self):
        from raft_ncup_tpu.inference.costs import CostLedger

        ledger = CostLedger(enabled=True)
        fwd = ShapeCachedForward(
            _DummyModel(), {}, cache_size=4, cost_ledger=ledger
        )
        return fwd, ledger

    def test_custom_key_meta_parse(self):
        """``custom()`` programs: StreamEngine's step carries a structured
        identity (its report's ``executable_memory`` filters on it) with
        the optional early-exit marker; any other custom key keeps the
        opaque kind."""
        meta = ShapeCachedForward._ledger_meta
        assert meta(("custom", "stream", 8, "f32")) == {
            "kind": "stream_step", "rows": 8, "policy": "f32",
        }
        assert meta(("custom", "stream", 8, "f32", ("earlyexit", 0.1))) == {
            "kind": "stream_step", "rows": 8, "policy": "f32",
            "earlyexit_tol": 0.1,
        }
        assert meta(("custom", "other", 2)) == {"kind": "custom"}
        assert meta(("custom", "pipe_tick", (1, 32, 32, 3), 8, 4, "f32")) == {
            "kind": "custom"
        }

    def test_records_costs_at_compile_time_only(self):
        fwd, ledger = self._fwd_with_ledger()
        img = np.zeros((1, 8, 10, 3), np.float32)
        fwd.forward_device(img, img, 2)
        assert fwd.stats["compiles"] == 1
        assert len(ledger) == 1
        entry = ledger.lookup(kind="forward", shape=(1, 8, 10, 3), iters=2)
        assert entry is not None
        assert entry["backend"] == jax.default_backend()
        assert entry["compile_ms"] is not None and entry["compile_ms"] > 0
        assert entry["flops"] is None or entry["flops"] >= 0
        assert isinstance(entry["memory_stats"], dict)
        # Warm calls touch the ledger no further (one entry, same key).
        before = ledger.keys()
        fwd.forward_device(img, img, 2)
        assert fwd.stats["hits"] == 1
        assert ledger.keys() == before

    def test_key_stable_across_rewarm_zero_recompiles(self):
        """The acceptance pin: same shape ⇒ same ledger key, and a
        re-warm of the warm executable performs ZERO XLA compiles."""
        from raft_ncup_tpu.analysis.guards import RecompileWatchdog

        fwd, ledger = self._fwd_with_ledger()
        img = np.zeros((1, 8, 10, 3), np.float32)
        jax.block_until_ready(fwd.forward_device(img, img, 2))
        keys_first = ledger.keys()
        assert len(keys_first) == 1
        with RecompileWatchdog() as wd:
            jax.block_until_ready(fwd.forward_device(img, img, 2))
        assert wd.count == 0
        assert ledger.keys() == keys_first
        # A fresh same-config cache writes the SAME ledger key (the key
        # is the executable's identity, not the instance's).
        fwd2 = ShapeCachedForward(
            _DummyModel(), {}, cache_size=4, cost_ledger=ledger
        )
        jax.block_until_ready(fwd2.forward_device(img, img, 2))
        assert ledger.keys() == keys_first

    def test_distinct_shapes_distinct_keys(self):
        fwd, ledger = self._fwd_with_ledger()
        a = np.zeros((1, 8, 10, 3), np.float32)
        b = np.zeros((1, 16, 10, 3), np.float32)
        fwd.forward_device(a, a, 2)
        fwd.forward_device(b, b, 2)
        fwd.forward_device(a, a, 4)
        assert len(ledger) == 3
        metas = [
            (e["meta"]["shape"], e["meta"]["iters"])
            for e in (ledger.entry(k) for k in ledger.keys())
        ]
        assert len(set(map(str, metas))) == 3

    def test_disabled_ledger_records_nothing(self):
        from raft_ncup_tpu.inference.costs import CostLedger

        ledger = CostLedger(enabled=False)
        fwd = ShapeCachedForward(
            _DummyModel(), {}, cache_size=4, cost_ledger=ledger
        )
        img = np.zeros((1, 8, 10, 3), np.float32)
        fwd.forward_device(img, img, 2)
        assert len(ledger) == 0
        assert fwd.stats["compiles"] == 1  # cache accounting unchanged

    def test_peak_table_and_mfu(self):
        """MFU is non-null for every backend with a peak entry (CPU
        included — a nominal per-core figure) and null ONLY for an
        unknown backend, never for 'we did not measure'."""
        from raft_ncup_tpu.inference import costs

        assert costs.peak_flops("cpu") > 0
        assert costs.peak_flops("tpu", device_kind="TPU v5 lite") == 197e12
        assert costs.peak_flops("tpu", device_kind="TPU v4") == 275e12
        with pytest.raises(KeyError, match="weird"):
            costs.peak_flops("tpu", device_kind="weird")
        assert costs.peak_flops("quantum") is None
        assert costs.peak_flops(None) is None
        assert costs.mfu(1e9, 3.0, 48e9) == pytest.approx(0.0625)
        assert costs.mfu(None, 3.0, 48e9) is None
        assert costs.mfu(1e9, 3.0, None) is None
        # Env override wins for CPU (autotuner/operator escape hatch).
        import os as _os

        _os.environ["RAFT_NCUP_CPU_PEAK_FLOPS"] = "1e12"
        try:
            assert costs.peak_flops("cpu") == 1e12
        finally:
            del _os.environ["RAFT_NCUP_CPU_PEAK_FLOPS"]

    def test_snapshot_is_json_able(self):
        import json as _json

        fwd, ledger = self._fwd_with_ledger()
        img = np.zeros((1, 8, 10, 3), np.float32)
        fwd.forward_device(img, img, 2)
        snap = _json.loads(_json.dumps(ledger.snapshot()))
        assert snap["enabled"] is True
        (entry,) = snap["entries"].values()
        assert entry["meta"]["shape"] == [1, 8, 10, 3]


# ------------------------------------------------ the eval pass's spans


class TestEvalPassSpans:
    """``_run_metric_pass`` records where the pass waits: the input
    pipeline's three ``input_*`` spans and ``eval_dispatch`` /
    ``eval_throttle_wait`` once per batch, ``eval_pull`` once per pass,
    and ``eval_pairs_total`` counted where ``eval_dispatch`` closes."""

    PER_BATCH = ("input_wait", "input_stage", "input_h2d", "eval_dispatch",
                 "eval_throttle_wait")

    @pytest.mark.parametrize("n,batch_size", [(5, 2), (4, 4), (3, 1)])
    def test_one_span_per_batch_and_pairs_counted(self, n, batch_size):
        from raft_ncup_tpu.evaluation import _run_metric_pass
        from raft_ncup_tpu.observability import Telemetry

        tel = Telemetry()
        fwd = ShapeCachedForward(_DummyModel(), {}, telemetry=tel)
        ds = _ListDataset(_mk_samples(n))
        for _ in range(2):  # two passes: the ids tell them apart
            _run_metric_pass(
                fwd, ds, kind="epe", iters=1, batch_size=batch_size,
                num_workers=2, telemetry=tel,
            )
        batches = -(-n // batch_size)
        assert tel.counter_value("eval_pairs_total") == 2 * n
        passes = {r["attrs"]["pass_id"] for r in tel.tracer.records("eval_pull")}
        assert len(passes) == 2
        for name in self.PER_BATCH:
            recs = tel.tracer.records(name)
            assert len(recs) == 2 * batches, name
            for pass_id in passes:
                mine = [r for r in recs if r["attrs"]["pass_id"] == pass_id]
                assert sorted(r["attrs"]["batch"] for r in mine) == list(range(batches))
            assert all(r["duration_ms"] >= 0 and r["t_s"] > 0 for r in recs)
        stages = tel.tracer.stage_summary()
        assert stages["input_wait"]["count"] == stages["eval_dispatch"]["count"]

    def test_process_hub_is_the_default_and_a_disabled_hub_records_nothing(self):
        from raft_ncup_tpu.evaluation import _run_metric_pass
        from raft_ncup_tpu.observability import Telemetry, set_telemetry

        ds = _ListDataset(_mk_samples(4))
        mine = Telemetry()
        prev = set_telemetry(mine)
        try:
            fwd = ShapeCachedForward(_DummyModel(), {})
            want = _run_metric_pass(fwd, ds, kind="epe", iters=1, batch_size=2)
        finally:
            set_telemetry(prev)
        assert mine.counter_value("eval_pairs_total") == 4
        assert len(mine.tracer.records("input_h2d")) == 2
        off = Telemetry(enabled=False)
        got = _run_metric_pass(
            ShapeCachedForward(_DummyModel(), {}, telemetry=off), ds,
            kind="epe", iters=1, batch_size=2, telemetry=off,
        )
        assert off.registry.names() == [] and off.tracer.records() == []
        np.testing.assert_array_equal(got, want)
