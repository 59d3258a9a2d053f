"""Load-adaptive anytime iteration budget with hysteresis.

RAFT refines flow iteratively: each GRU iteration improves the estimate,
and stopping early yields a coarser but structurally valid field
(PAPERS.md: arXiv:2003.12039 — "RAFT: Recurrent All-Pairs Field
Transforms"; the reference evaluates the same checkpoint at 12, 24 and
32 iterations). That makes iteration count a native latency/quality knob
the serving tier can turn under load — trade EPE for p99 the way
efficient-correlation work trades memory for resolution (PAPERS.md:
"Efficient All-Pairs Correlation Volume Sampling").

Two constraints shape the controller:

1. **The level set is small and FIXED** (``levels``, descending, e.g.
   ``(24, 16, 8)``). Every level is one compiled executable per (shape,
   batch) — a continuous knob would compile a fresh program per value
   and recompile-storm the exact burst it exists to absorb.
2. **Moves have hysteresis.** Degrading is immediate (occupancy ≥
   ``high_water`` ⇒ one level down — a burst must not wait), but
   recovering requires ``recover_patience`` CONSECUTIVE decisions at or
   below ``low_water``: the gap between the watermarks plus the patience
   window keeps the controller from flapping between two executables at
   a load sitting exactly on a threshold (each flap re-warms nothing —
   both programs stay cached — but flapping quality per-request is a
   worse client contract than a stable coarser answer).

The controller is pure host-side bookkeeping, driven once per batch
assembly with the queue depth the dispatcher just observed — no clock,
no device work, deterministic for tests (tests/test_serving.py pins the
drop/recover trajectories).
"""

from __future__ import annotations

from typing import List, Optional, Sequence


class IterationBudgetController:
    """Map admission-queue occupancy to a GRU iteration budget."""

    def __init__(
        self,
        levels: Sequence[int],
        capacity: int,
        high_water: float = 0.75,
        low_water: float = 0.25,
        recover_patience: int = 4,
    ):
        levels = tuple(int(x) for x in levels)
        if not levels or any(x <= 0 for x in levels):
            raise ValueError(f"iteration levels must be positive: {levels!r}")
        if list(levels) != sorted(levels, reverse=True):
            raise ValueError(
                f"iteration levels must be strictly descending: {levels!r}"
            )
        if not 0.0 <= low_water < high_water <= 1.0:
            raise ValueError(
                f"want 0 <= low_water < high_water <= 1, got "
                f"{low_water}/{high_water}"
            )
        self.levels = levels
        self.capacity = max(1, int(capacity))
        self.high_water = float(high_water)
        self.low_water = float(low_water)
        self.recover_patience = max(1, int(recover_patience))
        self._level = 0  # index into levels; 0 = full quality
        self._calm = 0  # consecutive at/below-low_water decisions
        self.drops = 0
        self.recoveries = 0
        self.slo_drops = 0  # drops where the SLO verdict was the cause
        self.decisions: List[int] = [0] * len(levels)  # per-level counts
        # Executed-iterations EWMA (early exit, docs/PERF.md): None until
        # the first observation — an unfed controller is BITWISE the
        # worst-case controller (expected_scale() == 1.0).
        self._exec_ewma: Optional[float] = None
        self.exec_alpha = 0.25

    @property
    def level(self) -> int:
        return self._level

    @property
    def iters(self) -> int:
        """Current budget without making a decision (reporting only)."""
        return self.levels[self._level]

    # ------------------------------------------- expected-iteration model

    def note_executed(self, executed_iters: float) -> None:
        """Feed one batch's mean EXECUTED iteration count (early exit,
        docs/PERF.md "Early exit"): the EWMA turns the per-batch counts
        the dispatch path already observes into the controller's model
        of what a request actually costs. Clamped into
        ``(1, levels[0])`` — a bogus observation (zero, negative, or
        above the top budget) must not corrupt the occupancy scale."""
        x = min(float(self.levels[0]), max(1.0, float(executed_iters)))
        if self._exec_ewma is None:
            self._exec_ewma = x
        else:
            a = self.exec_alpha
            self._exec_ewma = a * x + (1.0 - a) * self._exec_ewma

    @property
    def expected_iters(self) -> float:
        """The controller's per-request cost model: the executed-iters
        EWMA when early exit has been feeding it, else the worst case
        (the top level — exactly the pre-early-exit assumption)."""
        if self._exec_ewma is None:
            return float(self.levels[0])
        return self._exec_ewma

    def expected_scale(self) -> float:
        """Fraction of the worst-case budget a request is EXPECTED to
        cost (1.0 when never fed — the unfed controller is bitwise the
        PR-12 controller). Scales occupancy in :meth:`decide`: a queue
        of requests that exit after half their budget is only half the
        work the same depth represented under worst-case accounting, so
        the controller admits more depth at the same watermarks — more
        admitted load at the same p99."""
        return min(1.0, self.expected_iters / float(self.levels[0]))

    def decide(self, queue_depth: int, slo_degraded: bool = False) -> int:
        """One decision: observe ``queue_depth`` (and the SLO verdict),
        maybe move one level, return the iteration budget for the batch
        being assembled.

        ``slo_degraded`` is the second degrade input (observability/slo
        — docs/OBSERVABILITY.md): a paging burn rate degrades exactly
        like a high-water occupancy observation, immediately and with
        the same one-level-per-decision pacing — queue depth says "work
        is piling up HERE", the SLO verdict says "the objective is
        burning" (which queue depth alone misses when the damage shows
        as shed rate or tail latency rather than backlog). Recovery is
        the same earned-calm path for both: the SLO must stop paging
        AND occupancy must sit at/below low_water for the patience
        window.
        """
        # Occupancy is EXPECTED-WORK occupancy: raw depth scaled by the
        # executed-iters model (expected_scale() == 1.0 until early exit
        # feeds note_executed — worst-case accounting, the exact PR-12
        # behavior). The SLO verdict is deliberately NOT scaled: a
        # burning objective degrades immediately regardless of how cheap
        # the model thinks a request is.
        occ = min(
            1.0,
            (max(0, int(queue_depth)) / self.capacity)
            * self.expected_scale(),
        )
        if occ >= self.high_water or slo_degraded:
            self._calm = 0
            if self._level < len(self.levels) - 1:
                self._level += 1
                self.drops += 1
                if slo_degraded and occ < self.high_water:
                    # Occupancy alone would NOT have degraded here: this
                    # drop is the telemetry loop driving the knob.
                    self.slo_drops += 1
        elif occ <= self.low_water:
            self._calm += 1
            if self._calm >= self.recover_patience and self._level > 0:
                self._level -= 1
                self.recoveries += 1
                self._calm = 0
        else:
            # Between the watermarks: hold level, reset patience — a
            # recovery must be earned by sustained calm, not by load
            # oscillating through the low band.
            self._calm = 0
        self.decisions[self._level] += 1
        return self.levels[self._level]

    def summary(self) -> str:
        per = " ".join(
            f"{it}it={n}" for it, n in zip(self.levels, self.decisions)
        )
        return (
            f"budget: level={self._level} ({self.iters} iters) "
            f"expected={self.expected_iters:.1f} "
            f"drops={self.drops} recoveries={self.recoveries} [{per}]"
        )
