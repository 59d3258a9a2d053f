"""Iteration-pipelined inference (inference/pipe_schedule.py;
docs/SHARDING.md "Pipeline axis").

The pipeline's claims split cleanly into CPU-pinnable invariants and a
chip-window throughput claim; these tests pin everything in the first
bucket on the forced 8-virtual-device platform (tests/conftest.py):

- segment math: iteration splitting and the budget quantization rule
  (``serving/budget.py`` validates at construction),
- PARITY: the streamed pipeline (S=2, S=4) is tolerance-equal to the
  monolithic scan for both variants and both precisions — segmented
  and monolithic execution share one step body by construction
  (models/raft.py ``_make_step``), and the stream exercises every
  carry-handoff seam,
- S=1 is EXACTLY the monolithic path (delegation, forward cache keys,
  no pipe machinery),
- shape algebra is segmentation-invariant (eval_shape, no compiles),
- steady state is guard-clean (0 recompiles, 0 implicit host
  transfers) and the state operand is donated,
- the compiled tick's HLO carries the collective-permute handoff
  fingerprint (``parallel.mesh.collective_stats`` per-op breakout),
- the tick executable lands in the cost ledger with structured
  pipe_tick meta and the per-segment cost split.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import small_model_config
from raft_ncup_tpu.inference.costs import CostLedger
from raft_ncup_tpu.inference.pipe_schedule import (
    PipelinedForward,
    split_iters,
    validate_segment_levels,
)
from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
from raft_ncup_tpu.models import get_model
from raft_ncup_tpu.parallel.mesh import collective_stats, make_mesh
from raft_ncup_tpu.serving.budget import IterationBudgetController

HW = (32, 32)
ITERS = 4  # divisible by S in {1, 2, 4}


@pytest.fixture(scope="module")
def raft(request):
    cfg = small_model_config("raft", dataset="chairs")
    model = get_model(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, *HW, 3))
    return model, variables


@pytest.fixture(scope="module")
def dbl(request):
    cfg = small_model_config("raft_nc_dbl", dataset="chairs")
    model = get_model(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, *HW, 3))
    return model, variables


@pytest.fixture(scope="module")
def raft_mono(raft):
    model, variables = raft
    return ShapeCachedForward(model, variables)


@pytest.fixture(scope="module")
def pf_raft_s2(raft):
    model, variables = raft
    return PipelinedForward(
        model, variables, segments=2, cost_ledger=CostLedger(enabled=True)
    )


def _pairs(n, seed=0):
    g = np.random.default_rng(seed)
    return [
        (
            jnp.asarray(g.random((1, *HW, 3)) * 255.0, jnp.float32),
            jnp.asarray(g.random((1, *HW, 3)) * 255.0, jnp.float32),
        )
        for _ in range(n)
    ]


def _assert_stream_parity(outs, ref, rtol=1e-5, atol=1e-5):
    assert len(outs) == len(ref)
    for (lr_p, up_p), (lr_m, up_m) in zip(outs, ref):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(lr_p)),
            np.asarray(jax.device_get(lr_m)), rtol=rtol, atol=atol,
        )
        np.testing.assert_allclose(
            np.asarray(jax.device_get(up_p)),
            np.asarray(jax.device_get(up_m)), rtol=rtol, atol=atol,
        )


# ---------------------------------------------------------- segment math


class TestSegmentMath:
    def test_split_iters(self):
        assert split_iters(24, 1) == 24
        assert split_iters(24, 2) == 12
        assert split_iters(24, 4) == 6
        with pytest.raises(ValueError, match="does not split"):
            split_iters(24, 5)
        with pytest.raises(ValueError, match="segments must be >= 1"):
            split_iters(24, 0)

    def test_level_quantization_rule(self):
        # segments=1 imposes nothing — any valid level set passes.
        validate_segment_levels((24, 16, 8), 1)
        # The ISSUE's canonical counterexample: (24, 16, 8) with S=2
        # has segment length 12; 16 and 8 sit mid-segment.
        with pytest.raises(
            ValueError, match="quantize to the segment boundary"
        ):
            validate_segment_levels((24, 16, 8), 2)
        validate_segment_levels((24, 12), 2)
        validate_segment_levels((24, 18, 12, 6), 4)
        with pytest.raises(ValueError, match="does not split into 5"):
            validate_segment_levels((24, 12), 5)

    def test_error_names_a_valid_level_set(self):
        """The error must hand the operator a fix, not just a refusal."""
        with pytest.raises(ValueError, match=r"\(24, 12\)"):
            validate_segment_levels((24, 16, 8), 2)

    def test_serve_config_accepts_pipe_triple(self):
        """ServeConfig/StreamConfig mesh fields take (data, spatial,
        pipe) — resolve_config_mesh builds the 3-axis mesh from it and
        FlowServer passes the pipe size into the budget controller's
        quantization validation."""
        from raft_ncup_tpu.config import ServeConfig, StreamConfig

        assert ServeConfig(mesh=(1, 1, 2)).mesh == (1, 1, 2)
        assert StreamConfig(mesh=(1, 1, 2)).mesh == (1, 1, 2)
        with pytest.raises(ValueError, match="positive sizes"):
            ServeConfig(mesh=(1, 1, 0))
        with pytest.raises(ValueError, match="positive sizes"):
            ServeConfig(mesh=(1, 1, 2, 2))

    def test_budget_controller_validates_at_construction(self):
        with pytest.raises(
            ValueError, match="quantize to the segment boundary"
        ):
            IterationBudgetController((24, 16, 8), capacity=8, segments=2)
        # Default segments=1: the existing contract is untouched.
        ctl = IterationBudgetController((24, 16, 8), capacity=8)
        assert ctl.segments == 1
        ctl = IterationBudgetController((24, 12), capacity=8, segments=2)
        assert ctl.segments == 2
        assert ctl.decide(0) == 24  # quantized set still drives decisions


# ---------------------------------------------------------------- parity


class TestStreamParity:
    def test_raft_s2(self, raft, raft_mono, pf_raft_s2):
        _model, _variables = raft
        pairs = _pairs(3)
        ref = [raft_mono.forward_device(i1, i2, ITERS) for i1, i2 in pairs]
        _assert_stream_parity(pf_raft_s2.forward_many(pairs, ITERS), ref)

    def test_raft_s4(self, raft, raft_mono):
        model, variables = raft
        pf = PipelinedForward(model, variables, segments=4)
        assert pf.segments == 4 and pf.is_pipelined
        pairs = _pairs(5)
        ref = [raft_mono.forward_device(i1, i2, ITERS) for i1, i2 in pairs]
        _assert_stream_parity(pf.forward_many(pairs, ITERS), ref)

    def test_dbl_s2(self, dbl):
        model, variables = dbl
        mono = ShapeCachedForward(model, variables)
        pf = PipelinedForward(model, variables, segments=2)
        pairs = _pairs(3, seed=7)
        ref = [mono.forward_device(i1, i2, ITERS) for i1, i2 in pairs]
        _assert_stream_parity(pf.forward_many(pairs, ITERS), ref)

    def test_raft_s2_bf16(self, raft, raft_mono, pf_raft_s2):
        """Precision-policy override rides the pipeline: the bf16 tick
        is its own executable (policy fingerprint in the key) and
        matches the monolithic bf16 forward within bf16 slack."""
        pairs = _pairs(3, seed=3)
        ref = [
            raft_mono.forward_device(i1, i2, ITERS, policy="bf16_infer")
            for i1, i2 in pairs
        ]
        outs = pf_raft_s2.forward_many(pairs, ITERS, policy="bf16_infer")
        _assert_stream_parity(outs, ref, rtol=5e-2, atol=5e-2)

    def test_seam_composition_equals_full_scan(self, raft):
        """Model-level seam pin (no mesh): encode -> refine_segment x2
        -> finalize reproduces apply() exactly — the carry dict is the
        COMPLETE state at a segment boundary."""
        model, variables = raft
        g = np.random.default_rng(11)
        i1 = jnp.asarray(g.random((1, *HW, 3)) * 255.0, jnp.float32)
        i2 = jnp.asarray(g.random((1, *HW, 3)) * 255.0, jnp.float32)
        ref_lr, ref_up = model.apply(
            variables, i1, i2, iters=ITERS, test_mode=True
        )
        carry = model.encode(variables, i1, i2)
        carry = model.refine_segment(variables, carry, ITERS // 2)
        carry = model.refine_segment(variables, carry, ITERS // 2)
        lr, up = model.finalize(variables, carry)
        np.testing.assert_allclose(
            np.asarray(lr), np.asarray(ref_lr), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(up), np.asarray(ref_up), rtol=1e-6, atol=1e-6
        )


# ----------------------------------------------------- shapes/delegation


class TestShapesAndDelegation:
    @pytest.mark.parametrize("variant", ["raft", "raft_nc_dbl"])
    def test_eval_shape_segmentation_invariant(self, variant, raft, dbl):
        """Output ShapeDtypeStructs are identical for S in {1, 2, 4} and
        match the monolithic apply — pure shape algebra, no compiles."""
        model, variables = raft if variant == "raft" else dbl
        img = jax.ShapeDtypeStruct((1, *HW, 3), jnp.float32)

        def seg_run(s):
            def run(v, a, b):
                c = model.encode(v, a, b)
                for _ in range(s):
                    c = model.refine_segment(v, c, ITERS // s)
                return model.finalize(v, c)

            return jax.eval_shape(run, variables, img, img)

        mono = jax.eval_shape(
            lambda v, a, b: model.apply(
                v, a, b, iters=ITERS, test_mode=True
            ),
            variables, img, img,
        )
        shapes = {s: seg_run(s) for s in (1, 2, 4)}
        assert shapes[1] == shapes[2] == shapes[4] == mono

    def test_s1_is_exactly_the_monolithic_path(self, raft, raft_mono):
        model, variables = raft
        pf = PipelinedForward(model, variables, segments=1)
        assert not pf.is_pipelined and pf.mesh is None
        pairs = _pairs(2)
        outs = pf.forward_many(pairs, ITERS)
        ref = [raft_mono.forward_device(i1, i2, ITERS) for i1, i2 in pairs]
        _assert_stream_parity(outs, ref, rtol=0, atol=0)
        # Cache holds plain forward keys only — no pipeline machinery
        # was compiled (and no pipe mesh exists to fingerprint them).
        keys = list(pf.cache._fns)
        assert keys and all("pipe" not in str(k) for k in keys)
        assert keys[0][0] == "nomesh"

    def test_constructor_rejects_mismatch_and_mixed_mesh(self, raft):
        model, variables = raft
        mesh = make_mesh(
            data=1, spatial=1, pipe=2, devices=jax.devices()[:2]
        )
        with pytest.raises(ValueError, match="disagrees with mesh"):
            PipelinedForward(model, variables, mesh=mesh, segments=4)
        mixed = make_mesh(
            data=2, spatial=1, pipe=2, devices=jax.devices()[:4]
        )
        with pytest.raises(ValueError, match="data/spatial sizes of 1"):
            PipelinedForward(model, variables, mesh=mixed)

    def test_unsplittable_iters_raise_before_compiling(self, raft):
        model, variables = raft
        pf = PipelinedForward(model, variables, segments=2)
        with pytest.raises(ValueError, match="does not split"):
            pf.forward_many(_pairs(1), 5)
        assert pf.cache.stats["compiles"] == 0


# -------------------------------------------------- steady state + seams


class TestSteadyState:
    def test_guard_clean_window_and_executable_reuse(
        self, raft, pf_raft_s2, forbid_host_transfers
    ):
        """Second stream over the same shapes: zero recompiles, zero
        implicit host transfers, cache hits instead of compiles — the
        0/0 steady-state acceptance window."""
        from raft_ncup_tpu.analysis.guards import RecompileWatchdog

        pairs = _pairs(4, seed=5)
        outs = pf_raft_s2.forward_many(pairs, ITERS)  # warm (maybe hit)
        # Pre-warm the scalar-slice sync program outside the window.
        jax.device_get(outs[-1][1][0, 0, 0, 0])
        hits_before = pf_raft_s2.cache.stats["hits"]
        compiles_before = pf_raft_s2.cache.stats["compiles"]
        with RecompileWatchdog() as wd, forbid_host_transfers():
            outs = pf_raft_s2.forward_many(pairs, ITERS)
        jax.device_get(outs[-1][1][0, 0, 0, 0])
        assert wd.count == 0
        assert pf_raft_s2.cache.stats["compiles"] == compiles_before
        assert pf_raft_s2.cache.stats["hits"] > hits_before

    def test_state_donation(self, raft, pf_raft_s2):
        """The tick's stacked-carry operand is donated: after one tick
        the previous state's buffers are gone — steady-state memory is
        ONE stacked carry, not one per tick."""
        enc, tick, model, _pol = pf_raft_s2._programs(
            (1, *HW, 3), ITERS, None
        )
        carry_sds = pf_raft_s2._carry_struct((1, *HW, 3), model)
        state = pf_raft_s2._zero_state(carry_sds)
        fresh = pf_raft_s2._zero_fresh(carry_sds)
        leaf = jax.tree.leaves(state)[0]
        new_state, _lr, _up = tick(
            pf_raft_s2.variables, state, fresh
        )
        jax.block_until_ready(jax.tree.leaves(new_state))
        assert leaf.is_deleted()


# -------------------------------------------- collectives + cost ledger


class TestCollectiveFingerprint:
    def test_tick_hlo_shows_permute_per_seam(self, raft):
        """The compiled tick carries >= S-1 collective-permutes (one
        per carry-handoff seam; in practice one per carry leaf) and the
        by_op breakout reconciles with the aggregate counters."""
        model, variables = raft
        pf = PipelinedForward(model, variables, segments=4)
        cs = collective_stats(pf.tick_hlo((1, *HW, 3), ITERS))
        cp = cs["by_op"]["collective-permute"]
        assert cp["count"] >= pf.segments - 1
        assert cp["bytes"] > 0
        assert cs["collectives"] == sum(
            v["count"] for v in cs["by_op"].values()
        )
        assert cs["collective_bytes"] == sum(
            v["bytes"] for v in cs["by_op"].values()
        )

    def test_tick_text_reads_warmed_executable(self, raft, pf_raft_s2):
        """tick_text: the zero-compile inspection path bench uses —
        None before any stream, the warmed program's HLO after."""
        assert pf_raft_s2.tick_text((1, 64, 64, 3), ITERS) is None
        pf_raft_s2.forward_many(_pairs(2), ITERS)
        hlo = pf_raft_s2.tick_text((1, *HW, 3), ITERS)
        assert hlo is not None
        cs = collective_stats(hlo)
        assert cs["by_op"]["collective-permute"]["count"] >= 1


class TestCostLedger:
    def test_pipe_tick_meta_parse(self):
        meta = ShapeCachedForward._ledger_meta(
            ("custom", "pipe_tick", (1, 32, 32, 3), 8, 4, "f32")
        )
        assert meta == {
            "kind": "pipe_tick", "shape": (1, 32, 32, 3), "iters": 8,
            "segments": 4, "policy": "f32",
        }
        meta = ShapeCachedForward._ledger_meta(
            ("custom", "pipe_encode", (1, 32, 32, 3), "f32")
        )
        assert meta == {
            "kind": "pipe_encode", "shape": (1, 32, 32, 3),
            "policy": "f32",
        }
        # StreamEngine's step carries its identity too (its report's
        # ``executable_memory`` filters on it).
        assert ShapeCachedForward._ledger_meta(
            ("custom", "stream", 8, "f32")
        ) == {"kind": "stream_step", "rows": 8, "policy": "f32"}
        # Other custom keys keep the opaque kind.
        assert ShapeCachedForward._ledger_meta(("custom", "other", 2)) == {
            "kind": "custom"
        }

    def test_per_segment_split_is_derived(self):
        class _Compiled:
            def cost_analysis(self):
                return {"flops": 120.0, "bytes accessed": 44.0}

            def memory_analysis(self):
                raise NotImplementedError

        ledger = CostLedger(enabled=True)
        entry = ledger.record_compiled(
            "k", _Compiled(), backend="cpu", kind="pipe_tick", segments=4
        )
        assert entry["flops_per_segment"] == 30.0
        assert entry["bytes_per_segment"] == 11.0
        # segments=1 (or absent) derives nothing.
        entry = ledger.record_compiled(
            "k2", _Compiled(), backend="cpu", kind="forward"
        )
        assert "flops_per_segment" not in entry

    def test_stream_lands_structured_tick_entry(self, raft, pf_raft_s2):
        """After a real stream the tick executable's ledger entry is
        findable by structured meta — the provenance the bench row and
        flip_recommendations read."""
        pf_raft_s2.forward_many(_pairs(2), ITERS)
        entry = pf_raft_s2.cache.costs.lookup(
            kind="pipe_tick", segments=2
        )
        assert entry is not None
        assert entry["meta"]["iters"] == ITERS
        assert entry["meta"]["shape"] == (1, *HW, 3)
        assert "flops_per_segment" in entry
        assert pf_raft_s2.cache.costs.lookup(kind="pipe_encode") is not None
