"""Precision-policy subsystem (raft_ncup_tpu/precision/; docs/PRECISION.md).

The acceptance contract of ROADMAP item 3, pinned as tests:

- policy semantics: presets resolve, the pinned dtypes (master weights,
  outputs, coords, accumulators) really are pinned, configs validate;
- ``fits_vmem`` budgets by element size, so bf16 exactly halves every
  per-level byte count and re-qualifies levels f32 rejects;
- MEASURED parity: the bf16 presets' predictions sit within the
  test-pinned EPE budget of f32 on the synthetic set — for the plain
  forward, the serving front-end, and the streaming warm-start chain —
  and a short bf16_train run tracks the f32 loss trajectory within
  ``TRAIN_LOSS_RTOL`` while every master-weight leaf stays f32;
- the executable caches can never collide policies: same shape, two
  policies, two entries, two compiles.

Everything runs the tiny RAFT-small model at 40x48 (the test suite's
standard real-model scale) on the rigid synthetic set — real flow
magnitudes, sharp boundaries — so the budgets measure real refinement
behavior, not toy zeros.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import (
    ModelConfig,
    ServeConfig,
    StreamConfig,
    TrainConfig,
    small_model_config,
)
from raft_ncup_tpu.data.synthetic import SyntheticFlowDataset
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.precision import (
    BF16_INFER,
    F32,
    FORWARD_EPE_BUDGET,
    PRESETS,
    TRAIN_LOSS_RTOL,
    PrecisionPolicy,
    resolve_policy,
)

HW = (40, 48)
ITERS = 2


def _epe(a: np.ndarray, b: np.ndarray) -> float:
    return float(
        np.sqrt(((np.asarray(a, np.float64) - np.asarray(b, np.float64)) ** 2)
                .sum(-1)).mean()
    )


# ------------------------------------------------------------ policy unit


class TestPolicySemantics:
    def test_presets_resolve(self):
        assert resolve_policy(None) is F32
        assert resolve_policy("bf16_infer") is BF16_INFER
        assert resolve_policy(BF16_INFER) is BF16_INFER
        assert set(PRESETS) == {"f32", "bf16_infer", "bf16_train"}

    def test_unknown_preset_raises(self):
        with pytest.raises(ValueError, match="unknown precision"):
            resolve_policy("fp8")

    def test_master_weights_and_outputs_are_pinned(self):
        """The policy CONSTRUCTOR rejects narrow master weights and
        narrow outputs — the pins are structural, not conventions."""
        with pytest.raises(ValueError, match="param_dtype"):
            PrecisionPolicy(name="bad", param_dtype="bfloat16")
        with pytest.raises(ValueError, match="output_dtype"):
            PrecisionPolicy(name="bad", output_dtype="bfloat16")

    def test_pinned_dtypes_ignore_compute(self):
        for pol in PRESETS.values():
            assert pol.coord_jnp == jnp.float32
            assert pol.acc_jnp == jnp.float32
            assert pol.norm_jnp == jnp.float32
            assert pol.upsampler_jnp == jnp.float32
            assert pol.param_jnp == jnp.float32

    def test_module_dtype_and_itemsize(self):
        assert F32.module_dtype is None  # input-dtype passthrough
        assert BF16_INFER.module_dtype == jnp.bfloat16
        assert F32.corr_itemsize == 4
        assert BF16_INFER.corr_itemsize == 2

    def test_norm_constant_matches_policy_pin(self):
        """nn/layers.py's named constants ARE the policy pins — a drift
        between them would silently fork the authority."""
        from raft_ncup_tpu.nn.layers import NORM_DTYPE, PARAM_DTYPE

        assert jnp.dtype(PARAM_DTYPE) == F32.param_jnp
        assert jnp.dtype(NORM_DTYPE) == F32.norm_jnp

    def test_config_validation(self):
        with pytest.raises(ValueError, match="precision"):
            ModelConfig(precision="fp8")
        with pytest.raises(ValueError, match="precision"):
            ServeConfig(precision="fp8")
        with pytest.raises(ValueError, match="precision"):
            StreamConfig(precision="fp8")

    def test_legacy_mixed_precision_maps_to_bf16_infer(self):
        assert ModelConfig(mixed_precision=True).precision_policy is BF16_INFER
        assert ModelConfig().precision_policy is F32
        # An explicit preset wins over the legacy bool.
        cfg = ModelConfig(precision="bf16_train", mixed_precision=True)
        assert cfg.precision_policy.name == "bf16_train"

    def test_explicit_f32_flag_beats_legacy_bool(self):
        """--precision f32 next to --mixed_precision must force f32 (the
        CLI zeroes the legacy bool whenever --precision is given — an
        explicit 'f32' is otherwise indistinguishable from the unset
        default)."""
        import argparse

        from raft_ncup_tpu.cli import add_model_args, model_config_from_args

        p = argparse.ArgumentParser()
        add_model_args(p)
        a = p.parse_args(["--mixed_precision", "--precision", "f32"])
        cfg = model_config_from_args(a, dataset="sintel")
        assert cfg.precision_policy is F32
        a = p.parse_args(["--mixed_precision"])
        cfg = model_config_from_args(a, dataset="sintel")
        assert cfg.precision_policy is BF16_INFER

    def test_serve_stream_inherit_model_policy_by_default(self, tiny_setup):
        """ServeConfig/StreamConfig precision defaults to None =
        'inherit the model's own policy': wrapping a bf16-configured
        model must not silently serve f32."""
        import dataclasses

        from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
        from raft_ncup_tpu.models.raft import get_model

        model, variables, _ = tiny_setup
        assert ServeConfig().precision is None
        assert StreamConfig().precision is None
        m16 = get_model(
            dataclasses.replace(model.cfg, precision="bf16_infer")
        )
        fwd = ShapeCachedForward(m16, variables)  # the server's default
        assert fwd.policy.name == "bf16_infer"


# --------------------------------------------------- fits_vmem (satellite)


class TestFitsVmemItemsize:
    def test_bytes_shrink_with_itemsize(self):
        from raft_ncup_tpu.ops.corr_pallas import _level_vmem_bytes

        # The slab and the f1 blocks halve at bf16; the frac/out blocks
        # are float32 whatever the policy, and the bf16 sublane tile is
        # 16 rows, so the total lands between half and all of the f32
        # figure — counted as Mosaic allocates it.
        for h, w, c in ((46, 96, 256), (135, 240, 256), (17, 33, 128)):
            b16 = _level_vmem_bytes(h, w, c, 4, itemsize=2)
            f32 = _level_vmem_bytes(h, w, c, 4, itemsize=4)
            assert f32 / 2 < b16 < f32

    def test_bf16_doubles_the_onchip_threshold(self):
        """The dispatch-threshold contract: scanning level heights, the
        largest level that fits at bf16 holds about twice the bytes of
        the largest that fits at f32 — i.e. there is a band of levels
        that f32 rejects and bf16 keeps on-chip."""
        from raft_ncup_tpu.ops.corr_pallas import fits_vmem

        c, r = 256, 4
        max_f32 = max_bf16 = 0
        for h in range(8, 600, 4):
            w = 2 * h
            if fits_vmem(h, w, c, r):
                max_f32 = h
            if fits_vmem(h, w, c, r, dtype=jnp.bfloat16):
                max_bf16 = h
        assert 0 < max_f32 < max_bf16
        # Byte threshold doubles => area threshold doubles => linear
        # dimension grows ~sqrt(2) (padding shifts it slightly).
        assert max_bf16 >= 1.3 * max_f32
        # And the band really exists: a level just above the f32 cut
        # takes the kernel at bf16.
        band_h = max_f32 + 4
        assert not fits_vmem(band_h, 2 * band_h, c, r)
        assert fits_vmem(band_h, 2 * band_h, c, r, dtype=jnp.bfloat16)

    def test_banded_budget_shrinks_with_itemsize(self):
        """The band-budget extension of the itemsize contract: the
        BANDED tier's VMEM bytes (_banded_vmem_bytes — single-buffered
        band slab + query blocks) shrink at bf16 by the slab
        and f1 terms, for any band geometry."""
        from raft_ncup_tpu.ops.corr_pallas import _banded_vmem_bytes

        for h, w, c, br in (
            (136, 240, 256, 8), (272, 480, 256, 8), (68, 120, 128, 32),
        ):
            b16 = _banded_vmem_bytes(h, w, c, 4, br, itemsize=2)
            f32 = _banded_vmem_bytes(h, w, c, 4, br, itemsize=4)
            assert f32 / 2 < b16 < f32

    def test_bf16_buys_wider_bands(self):
        """Threshold ratio at the banded tier: bf16 halves the per-row
        slab bytes, so band_plan's auto choice gets wider bands (fewer
        bands, fewer slab DMAs) at the same budget — pinned at the 4K
        and 1080p level-0 shapes."""
        from raft_ncup_tpu.ops.corr_pallas import band_plan

        for h, w in ((272, 480), (136, 240)):
            f32_plan = band_plan(h, w, 256, 4)
            b16_plan = band_plan(h, w, 256, 4, dtype=jnp.bfloat16)
            assert f32_plan is not None and b16_plan is not None
            assert b16_plan[0] > f32_plan[0]  # wider bands
            assert b16_plan[1] <= f32_plan[1]  # never more bands

    def test_pallas_dispatch_uses_policy_dtype(self):
        """corr_lookup_pallas at a shape in the bf16-only band routes
        MORE levels to the kernel under the bf16 policy than under f32
        (trace-time dispatch counts; interpret mode, no TPU needed)."""
        from raft_ncup_tpu.ops import corr_pallas as cp

        rng = np.random.default_rng(5)
        B, H, W, C = 1, 8, 8, 16
        f1 = jnp.asarray(rng.normal(size=(B, H, W, C)), jnp.float32)
        f2 = jnp.asarray(rng.normal(size=(B, H, W, C)), jnp.float32)
        coords = jnp.asarray(
            rng.uniform(0, 7, size=(B, H, W, 2)), jnp.float32
        )
        out32 = cp.corr_lookup_pallas(f1, f2, coords, 3, 2, True)
        out16 = cp.corr_lookup_pallas(
            f1, f2, coords, 3, 2, True, jnp.bfloat16
        )
        assert out32.dtype == jnp.float32 and out16.dtype == jnp.float32
        # bf16 storage, f32 accumulation: small relative error only.
        np.testing.assert_allclose(
            np.asarray(out16), np.asarray(out32), rtol=0.05, atol=0.05
        )


# ------------------------------------------------------- model-level setup


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = small_model_config("raft", dataset="chairs")
    model = RAFT(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1,) + HW + (3,))
    ds = SyntheticFlowDataset(HW, length=4, seed=123, style="rigid")
    return model, variables, ds


def _stack(ds, idx):
    s = [ds.sample(i) for i in idx]
    img1 = np.stack([x["image1"] for x in s]).astype(np.float32)
    img2 = np.stack([x["image2"] for x in s]).astype(np.float32)
    gt = np.stack([x["flow"] for x in s]).astype(np.float32)
    return img1, img2, gt


# -------------------------------- cache keys (satellite) + forward parity


@pytest.fixture(scope="module")
def fwd_pair(tiny_setup):
    """ONE ShapeCachedForward driven under both policies on the same
    4-frame batch — the two compiles every test in this section shares
    (tier-1 budget: the suite runs against a hard wall clock, so the
    f32/bf16 executables compile once here, not once per test)."""
    from raft_ncup_tpu.inference.pipeline import ShapeCachedForward

    model, variables, ds = tiny_setup
    img1, img2, gt = _stack(ds, [0, 1, 2, 3])
    fwd = ShapeCachedForward(model, variables)
    out32 = jax.device_get(
        fwd.forward_device(img1, img2, ITERS, policy="f32")
    )
    out16 = jax.device_get(
        fwd.forward_device(img1, img2, ITERS, policy="bf16_infer")
    )
    return fwd, (img1, img2, gt), out32, out16


class TestPolicyCacheKeys:
    def test_two_policies_two_entries_two_compiles(self, fwd_pair):
        """Same shape, two policies: the LRU holds TWO executables and
        the compiles counter reads 2 — an f32 and a bf16 program can
        never collide on a shape key (the regression the policy
        fingerprint in the key exists to prevent)."""
        fwd, (img1, img2, _), _, _ = fwd_pair
        assert fwd.stats["compiles"] == 2
        assert len(fwd._fns) == 2
        # Repeat calls hit, never recompile; the instance policy (f32
        # default here) keys identically to naming it explicitly.
        hits0 = fwd.stats["hits"]
        fwd.forward_device(img1, img2, ITERS)
        fwd.forward_device(img1, img2, ITERS, policy="bf16_infer")
        assert fwd.stats["compiles"] == 2
        assert fwd.stats["hits"] == hits0 + 2


class TestForwardParity:
    def test_bf16_forward_within_epe_budget(self, fwd_pair):
        """The headline contract: bf16_infer's prediction sits within
        the test-pinned EPE budget of the f32 prediction on the rigid
        synthetic set, and the EPE-vs-ground-truth of the two runs
        agrees to the same budget."""
        _, (_, _, gt), (_, up32), (_, up16) = fwd_pair
        assert np.isfinite(up16).all()
        delta = _epe(up16, up32)
        assert 0.0 < delta <= FORWARD_EPE_BUDGET, delta
        # Metric-level agreement: the two runs' EPE-vs-gt differ by at
        # most the field budget (triangle inequality made concrete).
        assert abs(_epe(up16, gt) - _epe(up32, gt)) <= FORWARD_EPE_BUDGET

    def test_outputs_and_carry_stay_f32_under_bf16(self, fwd_pair):
        """Policy pins, observed at the output surface: the low-res
        flow (coordinate carry) and the upsampled field come back f32
        from the bf16 executable."""
        fwd, (img1, img2, _), _, _ = fwd_pair
        flow_lr, flow_up = fwd.forward_device(
            img1, img2, ITERS, policy="bf16_infer"
        )
        assert flow_lr.dtype == jnp.float32
        assert flow_up.dtype == jnp.float32

    def test_metric_accumulate_upcasts_to_f32(self):
        """The accumulator pin at the fold itself (no compile needed):
        a bf16 prediction folded into the f32 accumulator yields f32
        sums — bf16 forwards change the flow, never the metric
        arithmetic."""
        from raft_ncup_tpu.inference import metrics as metrics_mod

        flow16 = jnp.ones((1, 8, 8, 2), jnp.bfloat16)
        gt = jnp.zeros((1, 8, 8, 2), jnp.float32)
        acc = metrics_mod.accumulate(
            "epe", metrics_mod.init_acc("epe"), flow16, gt
        )
        assert acc.dtype == jnp.float32
        out = metrics_mod.finalize("epe", np.asarray(acc))
        assert np.isfinite(out["epe"])


# ----------------------------------------------------- serving parity


class TestServingParity:
    @pytest.mark.slow
    def test_bf16_server_within_budget_of_f32_forward(self, tiny_setup):
        """Slow tier (tier-1 runs against a hard wall clock and this
        compiles a server's own program set): the fast tier keeps the
        forward-parity budget + the policy-keyed cache contract, the
        CLI drive (.claude/skills/verify) and the guarded
        `serve_*_bf16` bench row re-measure this path end to end."""
        from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
        from raft_ncup_tpu.serving import FlowServer

        model, variables, ds = tiny_setup
        img1, img2, _ = _stack(ds, [1])
        cfg = ServeConfig(
            batch_sizes=(1,), iter_levels=(ITERS,),
            precision="bf16_infer",
        )
        with FlowServer(model, variables, cfg) as srv:
            r = srv.submit(img1[0], img2[0]).result(180)
        assert r.ok, r.status
        fwd = ShapeCachedForward(model, variables)
        _, ref = fwd(img1, img2, ITERS)
        delta = _epe(r.flow, ref[0])
        assert 0.0 < delta <= FORWARD_EPE_BUDGET, delta

    def test_report_names_the_policy(self, tiny_setup):
        from raft_ncup_tpu.serving import FlowServer

        model, variables, _ = tiny_setup
        cfg = ServeConfig(batch_sizes=(1,), iter_levels=(ITERS,),
                          precision="bf16_infer")
        with FlowServer(model, variables, cfg) as srv:
            assert srv.report()["precision"] == "bf16_infer"


    def test_serve_config_and_model_precision_build_one_executable_key(
        self, tiny_setup, monkeypatch
    ):
        """`ServeConfig.precision="bf16_infer"` over a float32 model and a
        model built under `bf16_infer` with `ServeConfig.precision=None`
        (inherit) resolve to ONE forward key, the policy's name in it: a
        deployment that sets the preset either way warms the same
        executable (PR 39)."""
        import dataclasses

        from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
        from raft_ncup_tpu.models.raft import get_model
        from raft_ncup_tpu.serving import FlowServer

        model, variables, ds = tiny_setup
        img1, img2, _ = _stack(ds, [1])
        keys = []
        monkeypatch.setattr(
            ShapeCachedForward, "_get",
            lambda self, key, build: keys.append(tuple(key)) or (lambda *a: None),
        )
        narrow = get_model(dataclasses.replace(model.cfg, precision="bf16_infer"))
        for m, precision in ((model, "bf16_infer"), (narrow, None), (model, None)):
            cfg = ServeConfig(batch_sizes=(1,), iter_levels=(ITERS,), precision=precision)
            want = "f32" if m is model and precision is None else "bf16_infer"
            with FlowServer(m, variables, cfg) as srv:
                assert srv.report()["precision"] == want
                srv._fwd.forward_device(img1, img2, ITERS)
        assert keys[0] == keys[1] and keys[0][-1] == "bf16_infer"
        assert keys[2][-1] == "f32" and keys[2][:-1] == keys[0][:-1]


# ------------------------------------------------ streaming warm-start


class TestStreamingParity:
    def _run_stream(self, model, variables, ds, precision):
        from raft_ncup_tpu.streaming import StreamEngine

        cfg = StreamConfig(
            capacity=1, frame_hw=HW, iters=ITERS, batch_sizes=(1,),
            precision=precision,
        )
        flows = []
        with StreamEngine(model, variables, cfg) as eng:
            if precision != "f32":
                assert eng._table["flow"].dtype == jnp.bfloat16
            else:
                assert eng._table["flow"].dtype == jnp.float32
            for i in range(2):
                s = ds.sample(i)
                r = eng.submit(
                    "cam0",
                    np.asarray(s["image1"], np.float32),
                    np.asarray(s["image2"], np.float32),
                    frame_index=i,
                ).result(180)
                assert r.ok, r.status
                flows.append(np.asarray(r.flow))
        return flows

    @pytest.mark.slow
    def test_bf16_warm_start_chain_within_budget(self, tiny_setup):
        """Two consecutive frames of one stream — the second warm-starts
        from the (bf16-stored) slot table. Every frame of the bf16
        engine sits within the EPE budget of the f32 engine's frame, so
        narrow state storage does not drift the warm chain. Slow tier
        (two engines' step programs): the slot-table dtype itself is
        asserted here, and the `stream_*_bf16` bench row + the chaos CLI
        drive re-measure the path end to end."""
        model, variables, ds = tiny_setup
        f32_flows = self._run_stream(model, variables, ds, "f32")
        bf16_flows = self._run_stream(model, variables, ds, "bf16_infer")
        for k, (a, b) in enumerate(zip(f32_flows, bf16_flows)):
            assert _epe(b, a) <= FORWARD_EPE_BUDGET, (k, _epe(b, a))


# ------------------------------------------------------- train parity


class TestTrainParity:
    def _run_short_train(self, precision, steps=5):
        from raft_ncup_tpu.parallel.step import (
            make_synthetic_batch,
            make_train_step,
        )
        from raft_ncup_tpu.training.state import create_train_state

        model_cfg = small_model_config(
            "raft", dataset="chairs", precision=precision
        )
        train_cfg = TrainConfig(
            stage="chairs", batch_size=2, image_size=HW, iters=ITERS,
            num_steps=steps, precision=precision,
        )
        model, state = create_train_state(
            jax.random.PRNGKey(7), model_cfg, train_cfg,
            image_shape=(1,) + HW + (3,),
        )
        step = make_train_step(model, train_cfg)
        losses = []
        for i in range(steps):
            batch = make_synthetic_batch(
                jax.random.PRNGKey(100 + i), 2, *HW
            )
            rng = jax.random.fold_in(jax.random.PRNGKey(7), i)
            state, metrics = step(state, batch, rng)
            losses.append(float(jax.device_get(metrics["loss"])))
        return state, losses

    @pytest.mark.slow
    def test_bf16_train_tracks_f32_loss_trajectory(self):
        """The phase-2 contract: a short bf16_train run's per-step loss
        trajectory stays within TRAIN_LOSS_RTOL of f32 (identical init,
        identical batches), and the master weights/optimizer/sentinel
        arithmetic never narrow. Slow tier: two fwd+bwd compiles (the
        suite's convention for its most expensive real-model runs —
        cf. the streaming bitwise-isolation tests)."""
        state32, l32 = self._run_short_train("f32")
        state16, l16 = self._run_short_train("bf16_train")
        assert all(np.isfinite(l16))
        np.testing.assert_allclose(l16, l32, rtol=TRAIN_LOSS_RTOL)
        # bf16 compute really ran: trajectories differ beyond float noise.
        assert max(abs(a - b) for a, b in zip(l16, l32)) > 0.0
        # f32 master weights: every param and Adam-moment leaf is f32.
        for leaf in jax.tree.leaves(state16.params):
            assert leaf.dtype == jnp.float32
        for leaf in jax.tree.leaves(state16.opt_state):
            if hasattr(leaf, "dtype") and jnp.issubdtype(
                leaf.dtype, jnp.floating
            ):
                assert leaf.dtype == jnp.float32
        # Sentinel arithmetic untouched by the preset.
        assert state16.sentinel["ema_grad_norm"].dtype == jnp.float32

    @pytest.mark.parametrize("program,precision,forms", [
        ("step", "bf16_train", {"multiply_reduce/bfloat16"}),
        ("step", "f32", {"multiply_reduce/float32"}),
        ("forward", "f32", {"multiply_reduce/float32"}),
        # the control: at this shape a forward-only program over a narrow
        # volume does take the other form, at levels 0-1 (16x64, 8x32; 4x16 is
        # under the size)
        ("forward", "bf16_infer", {"tap_sums/bfloat16", "multiply_reduce/bfloat16"}),
    ])
    def test_only_a_forward_only_narrow_volume_takes_the_tap_sums(
        self, program, precision, forms
    ):
        """The ``volume`` lookup's forward-only contraction
        (``ops/corr.py::_tap_sums``, PR 42) is traced by programs that are
        not differentiated and store a level narrower than they contract it
        in, and by no other: the training step traces its lookup three
        times over and pays for every form at every start (PR 41: +7.6 s of
        warm set-up), so under either preset it keeps the forms it had, as
        every float32 program does. Lowered, not compiled."""
        from raft_ncup_tpu.ops import corr
        from raft_ncup_tpu.parallel.step import make_train_step
        from raft_ncup_tpu.training.state import create_train_state

        # a grid of 64 / 32 / 16 / 8 columns: every level is stored at its own
        # width, so the tally names forms alone (``ops/corr.py::stored_width``)
        hw, batch = (128, 512), 1
        model_cfg = small_model_config("raft", dataset="chairs", precision=precision)
        images = jax.ShapeDtypeStruct((batch, *hw, 3), jnp.float32)
        if program == "step":
            train_cfg = TrainConfig(
                stage="chairs", batch_size=batch, image_size=hw, iters=ITERS,
                num_steps=5, precision=precision,
            )
            state = jax.eval_shape(lambda: create_train_state(
                jax.random.PRNGKey(7), model_cfg, train_cfg, image_shape=(1, *HW, 3),
            )[1])
            model = RAFT(model_cfg)
            data = {
                "image1": images, "image2": images,
                "flow": jax.ShapeDtypeStruct((batch, *hw, 2), jnp.float32),
                "valid": jax.ShapeDtypeStruct((batch, *hw), jnp.float32),
            }
            program, args = make_train_step(model, train_cfg), (
                state, data, jax.random.PRNGKey(0)
            )
        else:
            model = RAFT(model_cfg)
            variables = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0), (1, *HW, 3))
            )
            program, args = jax.jit(
                lambda v, a, b: model.apply(v, a, b, iters=ITERS, test_mode=True)
            ), (variables, images, images)
        corr.reset_contract_forms()  # what ``init`` traced is not the program's
        program.lower(*args)
        traced = corr.contract_forms()
        assert sorted(traced) == [f"level{lvl}" for lvl in range(4)]
        assert set(traced.values()) == forms
        assert traced["level0"] == max(forms)

    def test_step_cache_keys_on_precision(self):
        """make_train_step memoization cannot hand a bf16 config the f32
        executable: the model config (which carries `precision`) is in
        the cache key."""
        from raft_ncup_tpu.parallel.step import _step_cache_key

        cfg32 = small_model_config("raft", dataset="chairs")
        cfg16 = small_model_config(
            "raft", dataset="chairs", precision="bf16_train"
        )
        t = TrainConfig(stage="chairs", batch_size=2, image_size=HW)
        assert _step_cache_key(cfg32, t, None) != _step_cache_key(
            cfg16, t, None
        )


# -------------------------------------------------- evaluation surface


def test_validators_accept_precision(tiny_setup, tmp_path):
    """validate_synthetic runs end to end under an explicit bf16 policy
    and returns a finite EPE within the budget of the f32 pass."""
    from raft_ncup_tpu.evaluation import validate_synthetic

    model, variables, _ = tiny_setup
    kwargs = dict(
        iters=ITERS, batch_size=2, size_hw=HW, length=2, style="rigid",
    )
    r32 = validate_synthetic(model, variables, **kwargs)
    r16 = validate_synthetic(
        model, variables, precision="bf16_infer", **kwargs
    )
    key = "synthetic_rigid"
    assert np.isfinite(r16[key])
    assert abs(r16[key] - r32[key]) <= FORWARD_EPE_BUDGET


def test_get_model_registry_distinguishes_precisions(tiny_setup):
    """dataclasses.replace on precision reaches a distinct (cached)
    model whose modules compute at the preset's dtype."""
    from raft_ncup_tpu.models.raft import get_model

    model, _, _ = tiny_setup
    cfg16 = dataclasses.replace(model.cfg, precision="bf16_infer")
    m16 = get_model(cfg16)
    assert m16 is not model
    assert m16.policy.name == "bf16_infer"
    assert m16 is get_model(cfg16)  # lru-cached
