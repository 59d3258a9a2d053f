"""Async training pipeline: DevicePrefetcher + non-blocking Logger.

Pins the contracts the asynchronous train loop relies on:

- the prefetcher is a pure pipeline stage — loader order and batch
  contents come through untouched, worker errors surface at ``next()``,
  and shutdown mid-stream closes the wrapped generator;
- training through the prefetcher is BITWISE identical to the serial
  host→device path (the overlap is free — no numerics drift);
- ``Logger.push`` performs ZERO host transfers between ``sum_freq``
  boundaries (counted by instrumenting ``jax.device_get`` and the pushed
  values' ``__float__``).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import TrainConfig, small_model_config
from raft_ncup_tpu.data import DevicePrefetcher, FlowLoader, SyntheticFlowDataset
from raft_ncup_tpu.parallel import device_put_batch, make_mesh, make_train_step
from raft_ncup_tpu.parallel.mesh import batch_sharding
from raft_ncup_tpu.training.logger import Logger
from raft_ncup_tpu.training.state import create_train_state


def _host_batches(n, B=2, H=16, W=24, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "image1": rng.integers(0, 255, (B, H, W, 3)).astype(np.uint8),
            "image2": rng.integers(0, 255, (B, H, W, 3)).astype(np.uint8),
            "flow": rng.standard_normal((B, H, W, 2)).astype(np.float32),
            "valid": np.ones((B, H, W), np.float32),
            "extra_info": [("frame", i)],
        }
        for i in range(n)
    ]


class TestDevicePrefetcher:
    def test_preserves_order_and_contents(self):
        batches = _host_batches(6)
        with DevicePrefetcher(iter(batches), depth=2) as pf:
            out = list(pf)
        assert len(out) == len(batches)
        for got, want in zip(out, batches):
            assert "extra_info" not in got  # metadata dropped pre-transfer
            assert set(got) == {"image1", "image2", "flow", "valid"}
            for k in got:
                assert isinstance(got[k], jax.Array)
                assert got[k].dtype == want[k].dtype
                np.testing.assert_array_equal(np.asarray(got[k]), want[k])

    def test_matches_flowloader_stream(self):
        """Prefetching a FlowLoader stream yields the loader's own batches
        in the loader's own order (determinism per (seed, epoch, index))."""
        ds = SyntheticFlowDataset((16, 24), length=8, seed=3)

        def fresh_stream():
            return FlowLoader(
                ds, batch_size=2, seed=11, num_workers=2,
                shard_index=0, num_shards=1,
            ).batches()

        direct = fresh_stream()
        want = [next(direct) for _ in range(6)]
        direct.close()

        with DevicePrefetcher(fresh_stream(), depth=3) as pf:
            got = [next(pf) for _ in range(6)]
        for g, w in zip(got, want):
            w.pop("extra_info", None)
            assert set(g) == set(w)
            for k in g:
                np.testing.assert_array_equal(np.asarray(g[k]), w[k])

    def test_propagates_worker_exception(self):
        def stream():
            yield _host_batches(1)[0]
            raise RuntimeError("decode failed")

        pf = DevicePrefetcher(stream(), depth=2)
        next(pf)
        with pytest.raises(RuntimeError, match="decode failed"):
            next(pf)
        # After the raise the prefetcher is shut down, not wedged.
        assert not pf._thread.is_alive()

    def test_close_mid_stream_closes_generator(self):
        closed = threading.Event()

        def infinite():
            try:
                while True:
                    yield _host_batches(1)[0]
            finally:
                closed.set()

        pf = DevicePrefetcher(infinite(), depth=2)
        next(pf)
        next(pf)
        pf.close()
        assert closed.wait(timeout=5.0), "wrapped generator never closed"
        assert not pf._thread.is_alive()
        pf.close()  # idempotent
        with pytest.raises(StopIteration):
            next(pf)

    def test_close_unblocks_stalled_worker(self):
        """A consumer that stops pulling leaves the worker blocked on a
        full queue; close() must still stop and join it."""
        pf = DevicePrefetcher(iter(_host_batches(50)), depth=1)
        next(pf)
        time.sleep(0.2)  # let the worker fill the queue and block on put
        pf.close()
        assert not pf._thread.is_alive()

    def test_exhaustion_raises_stop_iteration(self):
        pf = DevicePrefetcher(iter(_host_batches(2)), depth=4)
        assert len(list(pf)) == 2
        with pytest.raises(StopIteration):
            next(pf)

    def test_rejects_zero_depth(self):
        with pytest.raises(ValueError):
            DevicePrefetcher(iter([]), depth=0)


class TestDevicePutBatch:
    def test_mesh_shardings_apply_single_process(self):
        mesh = make_mesh(data=4, spatial=2)
        shardings = batch_sharding(mesh)
        batch = {k: v for k, v in _host_batches(1, B=4, H=16, W=16)[0].items()
                 if k != "extra_info"}
        out = device_put_batch(batch, mesh, shardings)
        for k, v in out.items():
            assert v.sharding == shardings[k], k
            np.testing.assert_array_equal(np.asarray(v), batch[k])

    def test_no_shardings_default_placement(self):
        batch = {"a": np.arange(6, dtype=np.float32)}
        out = device_put_batch(batch, None, None)
        assert isinstance(out["a"], jax.Array)
        np.testing.assert_array_equal(np.asarray(out["a"]), batch["a"])


def test_loss_trajectory_bitwise_identical_with_prefetch():
    """>=3 steps: the async pipeline (device prefetch + device-accumulated
    metrics, no per-step host sync) reproduces the serial path's losses
    BIT FOR BIT — same executable, same inputs, no numerics drift."""
    B, H, W = 2, 16, 24
    mcfg = small_model_config(variant="raft")
    tcfg = TrainConfig(
        stage="chairs", lr=1e-4, num_steps=50, batch_size=B,
        image_size=(H, W), iters=2,
    )
    model, _ = create_train_state(jax.random.key(0), mcfg, tcfg)
    step = make_train_step(model, tcfg)  # one jit: both runs share it
    batches = _host_batches(4, B=B, H=H, W=W, seed=42)
    rngs = [jax.random.key(100 + i) for i in range(len(batches))]

    def fresh_state():
        _, state = create_train_state(jax.random.key(0), mcfg, tcfg)
        return state

    # Serial path: per-step host transfer + per-step float() sync.
    state = fresh_state()
    serial_losses = []
    for batch, rng in zip(batches, rngs):
        host = {k: v for k, v in batch.items() if k != "extra_info"}
        state, metrics = step(state, {k: jnp.asarray(v) for k, v in host.items()}, rng)
        serial_losses.append(float(metrics["loss"]))

    # Async path: prefetcher feeds device batches, losses stay on device
    # until one device_get at the end.
    state = fresh_state()
    async_losses = []
    with DevicePrefetcher(iter(batches), depth=2) as pf:
        for rng in rngs:
            state, metrics = step(state, next(pf), rng)
            async_losses.append(metrics["loss"])
    async_losses = [float(v) for v in jax.device_get(async_losses)]

    assert async_losses == serial_losses  # bitwise, not allclose


class _CountingScalar:
    """Device-scalar stand-in that counts host conversions."""

    floats = 0

    def __init__(self, v):
        self.v = v

    def __add__(self, other):
        return _CountingScalar(self.v + getattr(other, "v", other))

    __radd__ = __add__

    def __float__(self):
        _CountingScalar.floats += 1
        return float(self.v)


def test_logger_push_no_host_transfer_between_boundaries(tmp_path, monkeypatch):
    """Zero jax.device_get and zero float() between sum_freq boundaries;
    exactly one device_get at the boundary."""
    import raft_ncup_tpu.training.logger as logger_mod

    calls = {"device_get": 0}

    def counting_device_get(tree):
        calls["device_get"] += 1
        return tree  # pass-through keeps _CountingScalar leaves intact

    monkeypatch.setattr(logger_mod.jax, "device_get", counting_device_get)
    _CountingScalar.floats = 0

    log = Logger(str(tmp_path), sum_freq=4, use_tensorboard=False)
    for s in range(3):
        log.push(s, {"loss": _CountingScalar(float(s)),
                     "epe": _CountingScalar(2.0 * s)}, lr=1e-4)
    assert calls["device_get"] == 0
    assert _CountingScalar.floats == 0  # no per-push host sync

    log.push(3, {"loss": _CountingScalar(3.0), "epe": _CountingScalar(6.0)},
             lr=1e-4)
    assert calls["device_get"] == 1  # ONE pull for the whole window
    log.close()
    text = (tmp_path / "log.txt").read_text()
    assert "loss 1.5000" in text and "epe 3.0000" in text

    # The next window starts clean: accumulators were reset.
    assert log._acc == {} and log._acc_n == 0


def test_logger_push_device_arrays_end_to_end(tmp_path):
    """With real jax scalars the accumulated means are correct."""
    log = Logger(str(tmp_path), sum_freq=3, use_tensorboard=False)
    for s in range(3):
        log.push(s, {"loss": jnp.float32(s + 1)})
    log.close()
    assert "loss 2.0000" in (tmp_path / "log.txt").read_text()


class TestInputSpans:
    """The prefetcher's three spans, shared by the train and eval loops."""

    def test_each_span_once_per_batch_and_none_for_exhaustion(self):
        from raft_ncup_tpu.observability import Telemetry

        tel = Telemetry()
        with DevicePrefetcher(
            iter(_host_batches(3)), depth=2, telemetry=tel,
            span_attrs={"pass_id": "p0"},
        ) as pf:
            assert len(list(pf)) == 3
            with pytest.raises(StopIteration):
                next(pf)  # a later next() is not a batch either
        for name in ("input_stage", "input_h2d", "input_wait"):
            recs = tel.tracer.records(name)
            assert [r["attrs"]["batch"] for r in recs] == [0, 1, 2], name
            assert {r["attrs"]["pass_id"] for r in recs} == {"p0"}

    def test_stage_span_times_the_wrapped_iterators_wait(self):
        from raft_ncup_tpu.observability import Telemetry

        def slow():
            for b in _host_batches(2):
                time.sleep(0.05)
                yield b

        tel = Telemetry()
        with DevicePrefetcher(slow(), depth=1, telemetry=tel) as pf:
            assert len(list(pf)) == 2
        stage = tel.tracer.records("input_stage")
        assert all(r["duration_ms"] >= 45.0 for r in stage)
        # the consumer waited for the first batch at least that long too
        assert tel.tracer.records("input_wait")[0]["duration_ms"] >= 45.0

    def test_failed_wait_is_not_counted_as_a_batch(self):
        from raft_ncup_tpu.observability import Telemetry

        def broken():
            yield _host_batches(1)[0]
            raise ValueError("decode failed")

        tel = Telemetry()
        pf = DevicePrefetcher(broken(), depth=1, telemetry=tel)
        next(pf)
        with pytest.raises(ValueError, match="decode failed"):
            next(pf)
        assert len(tel.tracer.records("input_wait")) == 1
