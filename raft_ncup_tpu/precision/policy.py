"""The precision policy: the single authority for dtypes on the hot path.

Every compute dtype the model, the inference pipeline, the serving/
streaming tiers, and the bench touch is decided HERE, by one frozen
``PrecisionPolicy`` — flax-style ``param_dtype`` / ``compute_dtype`` /
``output_dtype`` plus the derived dtypes the policy deliberately PINS
regardless of preset (see the property docstrings). Hot-path modules
never spell a raw ``jnp.float32``/``jnp.bfloat16`` inline: graftlint
JGL009 enforces that they route through a policy (or a named, commented
module/class-level constant the policy asserts against).

Why bf16 is safe here (docs/PRECISION.md has the full argument): RAFT's
iterative refinement re-reads full-precision query COORDINATES from the
correlation pyramid every GRU iteration (arXiv:2003.12039), so bf16
compute error in one iteration perturbs the next iteration's *inputs*
but does not accumulate in a carried high-precision state — the error
is bounded per-iteration, which is what makes a measured EPE budget
(tests/test_precision.py) meaningful rather than hopeful. What must NOT
be bf16 is pinned by the policy itself:

- ``coord_dtype`` (f32): the query coordinates / low-res flow carry.
  This is the numerical backbone of the refinement; bf16's 8 mantissa
  bits cannot even represent integer pixel positions above 256.
- ``acc_dtype`` (f32): metric accumulators sum millions of per-pixel
  terms; bf16 sums stall at ~256 (JGL005's dtype-hygiene discipline).
- ``norm_dtype`` (f32): normalization statistics (variance of many
  terms) — the standard mixed-precision exception.
- ``upsampler_dtype`` (f32): the NCUP upsampler sits outside the
  reference's autocast region (core/raft_nc_dbl.py:161) and its
  normalized-conv confidences are ratio-of-sums arithmetic.
- ``param_dtype`` (f32 in every shipped preset): master weights. The
  bf16 *training* preset is bf16-compute-with-f32-master-weights; the
  optimizer, loss, grad-norm and anomaly-sentinel arithmetic all run on
  f32 leaves exactly as before (pinned by tests/test_precision.py).

Presets:

- ``f32``        — everything float32 (the historical behavior).
- ``bf16_infer`` — bf16 activations + bf16 correlation features/volume
  on the test-mode forward; f32 params/coords/outputs/metrics.
- ``bf16_train`` — the same compute dtypes selected for training
  (f32 master weights; f32 loss/grad/sentinel arithmetic falls out of
  the f32 param leaves). Kept as a distinct named preset so a config
  or a bench row says which *phase* opted in, and so the two knobs can
  diverge later without a config migration.

The correlation volume is the dominant memory term (Efficient All-Pairs
Correlation Volume Sampling, arXiv:2505.16942); ``compute_dtype``
halving its element size is also what raises the Pallas VMEM dispatch
thresholds in ``ops/corr_pallas.py::fits_vmem`` (itemsize-aware since
this subsystem landed) so higher pyramid levels stay on-chip at 1080p.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Union

import jax.numpy as jnp

# The dtypes a policy may name. Strings (not jnp dtypes) are stored so
# the frozen dataclass stays hashable, JSON-able, and importable without
# touching a backend.
_ALLOWED = ("float32", "bfloat16")

# Error budgets the bf16 presets are HELD to, vs the f32 preset on the
# synthetic set (mean end-point-error between the two predictions, in
# pixels, at eval shapes). These are the test-pinned contract
# (tests/test_precision.py measures the real deltas and asserts them
# under these bounds) and the thresholds flip_recommendations applies
# to a bench record's parity fields before recommending a default flip.
# Measured on CPU (bf16 emulated, worst-case rounding): forward deltas
# land around 0.05-0.15 px at 96x128/12it; budgets sit ~2-3x above the
# observed ceiling so they catch regressions, not noise. What the chip
# reads at the published sizes is beside them in docs/PRECISION.md
# ("Measured on the chip") and root PERF.md section 2 (the cell
# train_sintel_nc_bf16 and its limits, PR 37); the budgets stay as they are.
FORWARD_EPE_BUDGET = 0.5  # px: test-mode forward / serving / streaming
TRAIN_LOSS_RTOL = 0.15  # relative per-step loss-trajectory tolerance

# Early exit rides the same error-budget discipline (docs/PERF.md
# "Early exit"): the adaptive-compute path is HELD to this mean-EPE
# delta vs its own full-budget twin (same inputs, same weights, no
# detection) before a speedup may be recommended. The detection norm
# bounds remaining full-res drift by ~8*tol px per skipped iteration
# (the 8x upsample scales displacements), so a tolerance in the
# recommended range keeps the delta far inside this budget; the pinned
# value sits above detection-boundary noise, not above real quality
# loss (tests/test_earlyexit.py measures the actual deltas under it).
EARLYEXIT_EPE_BUDGET = 0.5  # px: early-exit vs full-budget twin


@dataclass(frozen=True)
class PrecisionPolicy:
    """Immutable dtype policy (flax-style param/compute/output triple).

    ``name`` doubles as the cache fingerprint: ``ShapeCachedForward``
    keys compiled executables on it, serving/streaming configs select
    presets by it, and bench rows are suffixed with it — two policies
    with different dtypes MUST have different names.
    """

    name: str
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    output_dtype: str = "float32"

    def __post_init__(self) -> None:
        for field in ("param_dtype", "compute_dtype", "output_dtype"):
            v = getattr(self, field)
            if v not in _ALLOWED:
                raise ValueError(
                    f"{field}={v!r} not in {_ALLOWED} (policy {self.name!r})"
                )
        if self.param_dtype != "float32":
            # Master weights are f32 in every supported preset: optimizer
            # moments, loss and sentinel arithmetic all key off the param
            # leaves' dtype, and bf16 master weights would silently halve
            # their precision too.
            raise ValueError(
                f"param_dtype must be 'float32' (master weights); "
                f"policy {self.name!r} asked for {self.param_dtype!r}"
            )
        if self.output_dtype != "float32":
            # Outputs feed metric accumulators, submission writers and
            # the serving response contract — all of which are defined
            # in f32.
            raise ValueError(
                f"output_dtype must be 'float32' (metrics/serving "
                f"contract); policy {self.name!r} asked for "
                f"{self.output_dtype!r}"
            )

    # ------------------------------------------------------- jnp dtypes

    @property
    def param_jnp(self):
        """Master-weight storage dtype (f32 in every shipped preset)."""
        return jnp.dtype(self.param_dtype)

    @property
    def compute_jnp(self):
        """Activation / conv / correlation compute dtype."""
        return jnp.dtype(self.compute_dtype)

    @property
    def output_jnp(self):
        """Final flow-field dtype (metrics/serving contract: f32)."""
        return jnp.dtype(self.output_dtype)

    @property
    def corr_jnp(self):
        """Correlation feature/volume dtype — the dominant memory term,
        deliberately the compute dtype so bf16 halves the volume and
        doubles the Pallas VMEM dispatch thresholds."""
        return self.compute_jnp

    @property
    def state_jnp(self):
        """Streaming slot-table recurrent-state dtype (prev low-res
        flow, optional GRU net): compute dtype, so the bf16 presets
        halve per-stream HBM. The warm-start chain upcasts to
        ``coord_dtype`` before the splat — storage is narrow, coordinate
        arithmetic is not."""
        return self.compute_jnp

    # ------------------------------------------------ pinned (non-knob)

    @property
    def coord_jnp(self):
        """Query-coordinate / low-res-flow-carry dtype: ALWAYS f32.
        The refinement's correctness argument rests on re-reading
        full-precision coordinates each iteration; bf16 cannot represent
        integer pixel positions above 256."""
        return jnp.dtype("float32")

    @property
    def acc_jnp(self):
        """Metric-accumulator dtype: ALWAYS f32 (JGL005 discipline —
        bf16 sums saturate at ~256 summands)."""
        return jnp.dtype("float32")

    @property
    def norm_jnp(self):
        """Normalization-statistics dtype: ALWAYS f32 (the standard
        mixed-precision exception; ``nn/layers.py::Norm`` asserts its
        module constant equals this)."""
        return jnp.dtype("float32")

    @property
    def upsampler_jnp(self):
        """NCUP/convex upsampler dtype: ALWAYS f32 (outside the
        reference's autocast region; normalized-conv confidence
        arithmetic is ratio-of-sums)."""
        return jnp.dtype("float32")

    # ------------------------------------------------------ conveniences

    @property
    def module_dtype(self) -> Optional[Any]:
        """What ``nn/`` modules receive as their ``dtype`` attribute:
        ``None`` for pure-f32 policies (modules follow the input dtype,
        the historical behavior — avoids gratuitous casts in the f32
        program) and the compute dtype otherwise."""
        if self.compute_dtype == "float32":
            return None
        return self.compute_jnp

    @property
    def corr_itemsize(self) -> int:
        """Bytes per correlation element — what
        ``ops/corr_pallas.py::fits_vmem`` budgets VMEM with."""
        return int(self.corr_jnp.itemsize)

    @property
    def is_f32(self) -> bool:
        return self.compute_dtype == "float32"

    def fingerprint(self) -> str:
        """Stable executable-cache key component (``ShapeCachedForward``,
        bench row suffixes)."""
        return self.name


F32 = PrecisionPolicy(name="f32")
BF16_INFER = PrecisionPolicy(name="bf16_infer", compute_dtype="bfloat16")
BF16_TRAIN = PrecisionPolicy(name="bf16_train", compute_dtype="bfloat16")

PRESETS: dict[str, PrecisionPolicy] = {
    p.name: p for p in (F32, BF16_INFER, BF16_TRAIN)
}

PRESET_NAMES = tuple(PRESETS)


def resolve_policy(
    spec: Union[str, PrecisionPolicy, None]
) -> PrecisionPolicy:
    """Resolve a preset name / policy / None (→ ``f32``) to a policy."""
    if spec is None:
        return F32
    if isinstance(spec, PrecisionPolicy):
        return spec
    try:
        return PRESETS[spec]
    except KeyError:
        raise ValueError(
            f"unknown precision preset {spec!r}; known: {PRESET_NAMES}"
        ) from None
