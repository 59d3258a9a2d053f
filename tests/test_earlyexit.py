"""Adaptive compute per request (docs/PERF.md "Early exit"): in-graph
per-sample convergence detection in the refinement scan.

The contracts pinned here:

- **Bitwise freeze.** A lane detected converged at iteration k commits
  its own k-th update and rides frozen (``jnp.where`` select) to the
  end of the budget — its flow is BITWISE the plain forward truncated
  at k iterations, even though the two come from different executables
  (the while_loop program vs the scan program).
- **Quality budget.** The early-exit forward's mean EPE against its own
  full-budget twin stays inside the pinned ``EARLYEXIT_EPE_BUDGET``
  (precision/policy.py), for f32 and bf16_infer.
- **Guard cleanliness.** Detection lives in-graph: a warm early-exit
  window performs ZERO implicit host transfers and ZERO recompiles —
  no host code ever inspects the convergence mask.
- **Expected-iteration budgeting.** ``IterationBudgetController``
  scales occupancy by the executed-iters EWMA — admitted depth before
  degrade RISES as the EWMA falls — while the unfed controller and the
  SLO degrade path keep their exact PR-12 semantics.

Tolerances are probed from the fixture weights' actual convergence
dynamics at runtime (untrained weights have no decaying deltas, so a
hard-coded threshold would silently stop splitting lanes when the init
changes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import ServeConfig, small_model_config
from raft_ncup_tpu.inference.costs import CostLedger
from raft_ncup_tpu.inference.pipeline import (
    ShapeCachedForward,
    env_earlyexit_tol,
)
from raft_ncup_tpu.models import get_model
from raft_ncup_tpu.precision import EARLYEXIT_EPE_BUDGET
from raft_ncup_tpu.serving import STATUS_OK, FlowServer
from raft_ncup_tpu.serving.budget import IterationBudgetController

HW = (32, 32)
B = 3
ITERS = 4


@pytest.fixture(scope="module")
def raft():
    cfg = small_model_config("raft", dataset="chairs")
    model = get_model(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, *HW, 3))
    return model, variables


@pytest.fixture(scope="module")
def fwd(raft):
    model, variables = raft
    return ShapeCachedForward(model, variables)


@pytest.fixture(scope="module")
def images():
    g = np.random.default_rng(7)
    return (
        jnp.asarray(g.random((B, *HW, 3)) * 255.0, jnp.float32),
        jnp.asarray(g.random((B, *HW, 3)) * 255.0, jnp.float32),
    )


def _dnorm1(fwd, i1, i2, policy=None):
    """Per-lane detection norm of the FIRST iteration — exactly what the
    in-graph detector sees at step 1: flow starts at zero, so
    ``|flow_lr(1)|`` mean IS ``|delta_1|`` mean. Probed at runtime so the
    tolerance choice tracks the fixture weights' real dynamics."""
    lr, _up = fwd.forward_device(i1, i2, 1, policy=policy)
    lr = np.asarray(jax.device_get(lr))
    return np.abs(lr).mean(axis=(1, 2, 3))


def _splitting_tol(d1):
    """A tolerance strictly between the lanes' first-iteration norms:
    at least one lane converges at iteration 1, at least one does not
    (untrained-weight deltas GROW with depth, so a lane that misses the
    first check never converges later — the split is stable)."""
    lo, hi = float(d1.min()), float(d1.max())
    assert lo < hi, f"degenerate probe: all lanes at {lo}"
    return (lo + hi) / 2.0


def _pull(x):
    return np.asarray(jax.device_get(x))


# ------------------------------------------------------- bitwise freeze


class TestBitwiseFreeze:
    def test_converged_lane_equals_truncated_run(self, fwd, images):
        """Lane i of the early-exit forward is BITWISE lane i of the
        plain forward at exec_iters[i] iterations — across executables
        (while_loop vs scan programs)."""
        i1, i2 = images
        tol = _splitting_tol(_dnorm1(fwd, i1, i2))
        lr, up, ex = fwd.forward_device(i1, i2, ITERS, early_exit_tol=tol)
        lr, up, ex = _pull(lr), _pull(up), _pull(ex)
        assert ex.min() >= 1 and ex.max() <= ITERS
        # The probed tolerance really split the batch: heterogeneous
        # executed counts, not an all-or-nothing window.
        assert ex.min() < ex.max()
        for i, k in enumerate(ex):
            ref_lr, ref_up = fwd.forward_device(i1, i2, int(k))
            np.testing.assert_array_equal(lr[i], _pull(ref_lr)[i])
            np.testing.assert_array_equal(up[i], _pull(ref_up)[i])

    def test_tiny_tol_runs_full_budget_bitwise(self, fwd, images):
        """A tolerance below every delta never fires: exec == budget and
        the result is bitwise the plain scan — detection costs no
        numerics when it does nothing."""
        i1, i2 = images
        lr, up, ex = fwd.forward_device(
            i1, i2, ITERS, early_exit_tol=1e-9
        )
        assert (_pull(ex) == ITERS).all()
        ref_lr, ref_up = fwd.forward_device(i1, i2, ITERS)
        np.testing.assert_array_equal(_pull(lr), _pull(ref_lr))
        np.testing.assert_array_equal(_pull(up), _pull(ref_up))


# -------------------------------------------------------- quality budget


class TestEpeParity:
    @pytest.mark.parametrize("policy", ["f32", "bf16_infer"])
    def test_epe_within_budget(self, fwd, images, policy):
        """Early exit vs the full-budget twin on the same inputs and
        weights: detection must fire AND the mean EPE delta must stay
        inside the pinned budget. Budget 2 here — each converged lane
        skips one refinement step, the granularity the EPE bound is
        written against (docs/PERF.md derives ~8*tol px per skipped
        step)."""
        i1, i2 = images
        tol = _splitting_tol(_dnorm1(fwd, i1, i2, policy=policy))
        _lr, up, ex = fwd.forward_device(
            i1, i2, 2, early_exit_tol=tol, policy=policy
        )
        _lr_f, up_f = fwd.forward_device(i1, i2, 2, policy=policy)
        ex = _pull(ex)
        assert ex.min() == 1  # detection fired on the converged lane(s)
        epe = float(
            np.sqrt(((_pull(up) - _pull(up_f)) ** 2).sum(-1)).mean()
        )
        assert epe <= EARLYEXIT_EPE_BUDGET, (
            f"{policy}: {epe:.4f} px vs budget {EARLYEXIT_EPE_BUDGET}"
        )


# ------------------------------------------------------ guard cleanliness


class TestGuards:
    def test_warm_window_zero_recompiles_zero_transfers(self, fwd, images):
        """With detection LIVE, a warm window is guard-clean: the mask,
        the while_loop condition, and the executed-iters counter all
        stay on device; the executable set is closed after warmup."""
        from raft_ncup_tpu.analysis.guards import (
            GuardStats,
            RecompileWatchdog,
            forbid_host_transfers,
        )

        i1, i2 = images
        tol = _splitting_tol(_dnorm1(fwd, i1, i2))
        # Warm the early-exit executable and the scalar-slice pull.
        out = fwd.forward_device(i1, i2, ITERS, early_exit_tol=tol)
        jax.device_get(out[1][0, 0, 0, 0])
        g = np.random.default_rng(23)
        stats = GuardStats()
        with RecompileWatchdog() as wd, forbid_host_transfers(
            stats, raise_on_violation=True
        ):
            outs = []
            for _ in range(3):
                j1 = jnp.asarray(g.random((B, *HW, 3)) * 255.0, jnp.float32)
                j2 = jnp.asarray(g.random((B, *HW, 3)) * 255.0, jnp.float32)
                outs.append(
                    fwd.forward_device(j1, j2, ITERS, early_exit_tol=tol)
                )
            # The one sanctioned explicit pull.
            jax.device_get(outs[-1][1][0, 0, 0, 0])
        assert wd.count == 0
        assert stats.host_transfers == 0


# ------------------------------------------------------------- API edges


class TestApiContracts:
    def test_detection_off_path_unchanged(self, fwd, images):
        """No tolerance → the exact pre-existing contract: a 2-tuple
        from a 4-tuple cache key (zero churn for existing callers)."""
        i1, i2 = images
        out = fwd.forward_device(i1, i2, ITERS)
        assert len(out) == 2

    def test_apply_validation(self, raft, images):
        model, variables = raft
        i1, i2 = images
        with pytest.raises(ValueError, match="test_mode"):
            model.apply(
                variables, i1, i2, iters=2, early_exit_tol=0.1
            )
        with pytest.raises(ValueError, match="early_exit_tol"):
            model.apply(
                variables, i1, i2, iters=2, test_mode=True,
                return_exec_iters=True,
            )

    def test_tolerances_are_distinct_executables(self, raft, images):
        """The tolerance is baked into the compiled loop condition, so
        each tolerance is its own cache entry — and the detection-off
        key stays a plain 4-tuple alongside them. The same fresh
        instance also pins the cost-ledger meta (one executable set,
        both contracts — compiles are the expensive part of this
        file)."""
        model, variables = raft
        led = CostLedger(enabled=True)
        fwd = ShapeCachedForward(model, variables, cost_ledger=led)
        i1, i2 = images
        fwd.forward_device(i1, i2, 2, early_exit_tol=0.5)
        fwd.forward_device(i1, i2, 2, early_exit_tol=0.25)
        fwd.forward_device(i1, i2, 2)
        assert fwd.stats["compiles"] == 3
        fwd.forward_device(i1, i2, 2, early_exit_tol=0.5)
        assert fwd.stats["hits"] == 1
        # Ledger meta: the threshold rides the executable entry, so
        # flip_recommendations (and the autotuner after it) can judge
        # EPE-vs-speedup against the exact tolerance that compiled.
        entry = led.lookup(kind="forward", earlyexit_tol=0.5)
        assert entry is not None
        assert entry["meta"]["iters"] == 2
        # The detection-off executable's meta carries NO tolerance.
        plain = led.lookup(kind="forward", iters=2, earlyexit_tol=None)
        assert plain is not None
        assert "earlyexit_tol" not in plain["meta"]

    def test_env_chokepoint(self, monkeypatch):
        monkeypatch.delenv("RAFT_NCUP_EARLYEXIT", raising=False)
        assert env_earlyexit_tol() is None
        monkeypatch.setenv("RAFT_NCUP_EARLYEXIT", "1")
        monkeypatch.setenv("RAFT_NCUP_EARLYEXIT_TOL", "0.125")
        assert env_earlyexit_tol() == 0.125


# ----------------------------------------------- expected-iteration budget


class TestBudgetEwma:
    LEVELS = (8, 4)
    CAP = 10

    def _ctl(self, **kw):
        return IterationBudgetController(
            self.LEVELS, capacity=self.CAP, high_water=0.75,
            low_water=0.25, recover_patience=2, **kw,
        )

    def test_unfed_controller_is_worst_case(self):
        """Never-fed → expected == top level, scale == 1.0: occupancy
        arithmetic (and therefore every decide trajectory) is bitwise
        the pre-early-exit controller."""
        ctl = self._ctl()
        assert ctl.expected_iters == 8.0
        assert ctl.expected_scale() == 1.0
        assert ctl.decide(8) == 4  # 0.8 >= 0.75: degrades, as before
        assert ctl.drops == 1

    def test_admitted_depth_rises_as_ewma_falls(self):
        """The tentpole serving claim: a queue of early-exiting requests
        is cheaper than its depth suggests, so the SAME depth that
        degrades the worst-case controller holds full quality once the
        executed-iters EWMA reflects the real cost."""
        ctl = self._ctl()
        for _ in range(32):  # converge the EWMA to ~2 of 8 iters
            ctl.note_executed(2.0)
        assert ctl.expected_iters == pytest.approx(2.0, abs=1e-3)
        assert ctl.expected_scale() == pytest.approx(0.25, abs=1e-3)
        # Depth 8 of 10: worst-case occupancy 0.8 (degrades, previous
        # test); expected-work occupancy 0.8 * 0.25 = 0.2 (holds).
        assert ctl.decide(8) == 8
        assert ctl.drops == 0

    def test_slo_degrade_not_scaled(self):
        """A burning SLO degrades immediately no matter how cheap the
        model thinks a request is — the PR-12 page semantics."""
        ctl = self._ctl()
        for _ in range(32):
            ctl.note_executed(1.0)
        assert ctl.decide(0, slo_degraded=True) == 4
        assert ctl.drops == 1 and ctl.slo_drops == 1

    def test_note_executed_clamps_and_smooths(self):
        ctl = self._ctl()
        ctl.note_executed(0.0)  # bogus: clamps to 1
        assert ctl.expected_iters == 1.0
        ctl.note_executed(99.0)  # bogus: clamps to levels[0]
        assert ctl.expected_iters == pytest.approx(
            0.25 * 8.0 + 0.75 * 1.0
        )

    def test_recovery_hysteresis_preserved(self):
        """Earned-calm recovery is untouched by the cost model: the
        scaled occupancy feeds the SAME watermark machinery."""
        ctl = self._ctl()
        assert ctl.decide(8) == 4
        assert ctl.decide(1) == 4  # calm 1
        assert ctl.decide(1) == 8  # calm 2 == patience: recovers
        assert ctl.recoveries == 1


# ----------------------------------------------------- server integration


class TestServerIntegration:
    def test_early_exit_serving_end_to_end(self, raft, fwd, images, monkeypatch):
        """The env knob turns detection on at server construction; the
        response flow is bitwise the direct early-exit forward, the
        executed-iters histogram fills, and the budget controller's
        expected-iters model moves off worst case."""
        model, variables = raft
        i1, i2 = images
        tol = _splitting_tol(_dnorm1(fwd, i1, i2))
        monkeypatch.setenv("RAFT_NCUP_EARLYEXIT", "1")
        monkeypatch.setenv("RAFT_NCUP_EARLYEXIT_TOL", repr(float(tol)))
        cfg = ServeConfig(
            queue_capacity=8, batch_sizes=(1,), iter_levels=(ITERS, 2),
            recover_patience=2,
        )
        img1 = np.asarray(i1[0])
        img2 = np.asarray(i2[0])
        srv = FlowServer(model, variables, cfg)
        try:
            assert srv._earlyexit_tol == pytest.approx(float(tol))
            rs = [
                srv.submit(img1, img2).result(120) for _ in range(3)
            ]
        finally:
            srv.drain()
        assert [r.status for r in rs] == [STATUS_OK] * 3
        _lr, ref_up, ref_ex = fwd.forward_device(
            i1[:1], i2[:1], ITERS, early_exit_tol=float(tol)
        )
        np.testing.assert_array_equal(rs[0].flow, _pull(ref_up)[0])
        hist = srv._tel.registry.get("serve_exec_iters")
        assert hist is not None and hist.count == 3
        report = srv.report()
        assert report["budget_expected_iters"] == pytest.approx(
            float(_pull(ref_ex)[0])
        )
