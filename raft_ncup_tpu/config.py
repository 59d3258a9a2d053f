"""Typed, immutable configuration for models, training, and data.

The reference drives everything through argparse plus a reflective flag
generator (reference: core/utils/args.py:8-114) and mutates ``args`` from
inside model constructors (reference: core/raft.py:32-42). Here the full
used surface of those flags (reference: train_raft_nc_things.sh:19-50) is
captured as frozen dataclasses resolved *before* model construction.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Sequence

# The FlyingChairs train/val split is defined by a 22,871-line 1/2-label
# file the reference ships at its root (reference: chairs_split.txt,
# loaded at core/datasets.py:128). It is vendored as package data so the
# chairs stage works out of the box (22,232 train / 640 val pairs).
PACKAGED_CHAIRS_SPLIT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "chairs_split.txt"
)


@dataclass(frozen=True)
class UpsamplerConfig:
    """Configuration of the final flow upsampler.

    Mirrors the capability surface of the reference upsampler factory
    (reference: core/upsampler.py:10-72) and the NConvUNet / weights-net
    constructor flags (reference: train_raft_nc_things.sh:31-50).
    """

    # 'nconv' (NCUP) or 'bilinear'. The RAFT baseline's convex
    # upsampler is part of the model itself, not this registry — as in the
    # reference (core/raft.py:73-84).
    kind: str = "nconv"
    # Upsampling factor applied by the upsampler itself. The NCUP path does
    # nearest x2 first and NCUP x4 after (reference: core/raft_nc_dbl.py:110).
    scale: int = 4
    use_data_for_guidance: bool = True
    channels_to_batch: bool = True
    use_residuals: bool = False
    est_on_high_res: bool = False

    # --- interpolation (NConvUNet) net (reference: core/nconv_modules.py:25-92)
    channels_multiplier: int = 2
    num_downsampling: int = 1
    encoder_filter_sz: int = 5
    decoder_filter_sz: int = 3
    out_filter_sz: int = 1
    use_bias: bool = False
    data_pooling: str = "conf_based"  # 'conf_based' | 'max_pooling'
    shared_encoder: bool = True
    use_double_conv: bool = False
    pos_fn: str = "softplus"  # 'softplus' | 'exp' | 'sigmoid' | 'softmax'

    # --- weights estimation net (reference: core/interp_weights_est.py:10-82)
    weights_est_net: str = "simple"  # 'simple' | 'unet' | 'binary'
    weights_est_num_ch: tuple[int, ...] = (64, 32)
    weights_est_filter_sz: tuple[int, ...] = (3, 3, 1)
    weights_est_dilation: tuple[int, ...] = (1, 1, 1)


@dataclass(frozen=True)
class ModelConfig:
    """Model architecture configuration.

    ``variant`` selects between the working model set of the reference:
    'raft' (reference: core/raft.py) and 'raft_nc_dbl' (reference:
    core/raft_nc_dbl.py). hidden/context dims and correlation geometry
    follow reference: core/raft.py:29-39.
    """

    variant: str = "raft_nc_dbl"  # 'raft' | 'raft_nc_dbl'
    small: bool = False
    dropout: float = 0.0
    # Precision-policy preset (raft_ncup_tpu/precision/; docs/PRECISION.md):
    # 'f32' | 'bf16_infer' | 'bf16_train'. The resolved PrecisionPolicy is
    # the single authority for every dtype on the hot path — module compute,
    # correlation volume, Pallas VMEM budgeting — with coords/metrics/
    # upsampler/master-weights pinned f32 by the policy itself.
    precision: str = "f32"
    # Legacy bool knob, kept for the reference CLI surface
    # (--mixed_precision): True with the default precision resolves to
    # the 'bf16_infer' preset. DELIBERATE divergence from the reference's
    # CUDA AMP autocast (core/raft.py:100-112): under the policy the
    # correlation volume now narrows too — it is the dominant memory
    # term, and parity is test-pinned rather than assumed
    # (docs/PRECISION.md; CHANGES.md PR 7). An explicit `precision` wins
    # (the CLI sets mixed_precision=False whenever --precision is given).
    mixed_precision: bool = False
    # align_corners for the bilinear x8 upsampling used by the small/no-mask
    # path (reference: core/raft.py:134; fixes the upflow8 signature bug
    # noted in SURVEY.md §0.3).
    align_corners: bool = True
    corr_levels: int = 4
    corr_radius: int = 4
    # 'volume' materializes the all-pairs volume (reference semantics,
    # core/corr.py:13-21); 'onthefly' recomputes windowed correlation per
    # lookup (memory-efficient for 1080p); 'pallas' = fused TPU kernel.
    corr_impl: str = "volume"
    # Dataset the model is configured for. Controls BatchNorm in the NCUP
    # weights-estimation net: ON for sintel, OFF otherwise (reference:
    # core/upsampler.py:41-46 — and carried everywhere to avoid the
    # reference's missing-``args.dataset`` crash, SURVEY.md §0.2).
    dataset: str = "sintel"
    # Freeze the RAFT trunk and train only the NCUP upsampler (reference:
    # core/raft_nc_dbl.py:70-72).
    freeze_raft: bool = False
    upsampler: UpsamplerConfig = field(default_factory=UpsamplerConfig)

    def __post_init__(self) -> None:
        if self.variant not in ("raft", "raft_nc_dbl"):
            raise ValueError(f"unknown model variant: {self.variant!r}")
        from raft_ncup_tpu.precision import resolve_policy

        resolve_policy(self.precision)  # raises on an unknown preset

    @property
    def precision_policy(self):
        """The resolved :class:`~raft_ncup_tpu.precision.PrecisionPolicy`
        (the legacy ``mixed_precision`` bool maps onto 'bf16_infer' when
        no explicit preset was chosen)."""
        from raft_ncup_tpu.precision import resolve_policy

        if self.precision == "f32" and self.mixed_precision:
            return resolve_policy("bf16_infer")
        return resolve_policy(self.precision)

    @property
    def hidden_dim(self) -> int:
        return 96 if self.small else 128

    @property
    def context_dim(self) -> int:
        return 64 if self.small else 128

    @property
    def fnet_dim(self) -> int:
        return 128 if self.small else 256

    @property
    def resolved_corr_radius(self) -> int:
        # reference: core/raft.py:29-39 — the model overrides the radius.
        return 3 if self.small else self.corr_radius

    @property
    def corr_planes(self) -> int:
        r = self.resolved_corr_radius
        return self.corr_levels * (2 * r + 1) ** 2


@dataclass(frozen=True)
class TrainConfig:
    """Training hyperparameters (reference: train.py:264-297 defaults and
    the shipped launch scripts, e.g. train_raft_nc_things.sh:24-31)."""

    name: str = "raft"
    stage: str = "chairs"  # 'chairs' | 'things' | 'sintel' | 'kitti'
    lr: float = 2e-5
    num_steps: int = 100_000
    batch_size: int = 6
    image_size: tuple[int, int] = (384, 512)
    iters: int = 12
    wdecay: float = 5e-5
    epsilon: float = 1e-8
    clip: float = 1.0
    gamma: float = 0.8
    max_flow: float = 400.0
    optimizer: str = "adamw"  # 'adamw' | 'adam'
    scheduler: str = "cyclic"  # 'cyclic' (OneCycle-linear) | 'step'
    scheduler_step: int = 20_000
    add_noise: bool = False
    validation: tuple[str, ...] = ()
    val_freq: int = 5000
    sum_freq: int = 100
    seed: int = 1234
    restore_ckpt: str | None = None
    load_pretrained: str | None = None
    checkpoint_dir: str = "checkpoints"
    # parallelism: data-parallel size (None = all devices) and spatial size.
    data_parallel: int | None = None
    spatial_parallel: int = 1
    # --- divergence sentinel (resilience/anomaly.py; docs/RESILIENCE.md).
    # Folded into the jitted step when enabled: non-finite loss/grad and
    # grad-norm spikes become skip-updates (state unchanged), counted on
    # device; K consecutive bad steps halt the run with a rollback.
    # Default ON so the CLI, the library, and the bench all compile the
    # SAME production step program — a sentinel-off bench would never see
    # a sentinel-induced throughput regression.
    anomaly_sentinel: bool = True
    sentinel_spike_factor: float = 20.0  # grad_norm > factor * EMA = spike
    sentinel_ema_decay: float = 0.99
    sentinel_warmup: int = 10  # good steps before spike detection arms
    sentinel_halt_after: int = 10  # K consecutive bad steps => halt
    # Training precision preset (docs/PRECISION.md): 'f32' or 'bf16_train'
    # (bf16 module compute with f32 master weights; loss/grad-norm/
    # sentinel arithmetic stays f32 because the param leaves do). The CLI
    # threads this into ModelConfig.precision so the step program and the
    # policy agree; bookkept here so checkpoints' resume metadata and the
    # bench's train rows can say which phase opted in.
    precision: str = "f32"

    def __post_init__(self) -> None:
        from raft_ncup_tpu.precision import resolve_policy

        resolve_policy(self.precision)  # raises on an unknown preset

    @property
    def total_schedule_steps(self) -> int:
        # reference: train.py:93-94 — OneCycle over num_steps + 100.
        return self.num_steps + 100


@dataclass(frozen=True)
class DataConfig:
    """Dataset roots and pipeline knobs (reference: core/datasets.py)."""

    root_chairs: str = "datasets/FlyingChairs_release/data"
    root_things: str = "datasets/FlyingThings3D"
    root_sintel: str = "datasets/Sintel"
    root_kitti: str = "datasets/KITTI"
    root_hd1k: str = "datasets/HD1k"
    chairs_split_file: str = PACKAGED_CHAIRS_SPLIT
    compressed_ft: bool = False
    num_workers: int = 2
    prefetch: int = 2
    # Device-side prefetch depth: host batches are moved to device this
    # many steps ahead of compute (DevicePrefetcher). >= 2 keeps one batch
    # in flight while the next transfers, so the accelerator never waits
    # on host→device transfer in steady state.
    device_prefetch: int = 2
    # Transient-IO resilience (resilience/retry.py): failed dataset reads
    # are retried with exponential backoff this many times before the
    # sample is quarantined and substituted; accounting lands in log.txt.
    io_retries: int = 3
    io_retry_backoff_s: float = 0.05
    # --- eval/inference pipeline (inference/pipeline.py) ----------------
    # Bound on the shape-cached compiled eval executables (LRU). Each
    # distinct (shape with the batch, native shape, iters, metric kind)
    # compiles once. A validation pass needs one a native size
    # (pipeline.uniform_batches groups by size across the stream and a
    # masked pass fills the remainders): KITTI-2015's four sizes fit. The
    # bound still protects the paths that dispatch frame by frame over
    # footage of many sizes (the submission writers, warm-start
    # validation) and a data set with more sizes than this: evictions are
    # counted, logged loudly and published per pass (eval_pass_programs).
    eval_cache_size: int = 8
    # Round padded eval shapes up to multiples of this bucket (0 = off:
    # every frame is padded to its own multiple of 8, as upstream pads).
    # Collapses many native sizes onto a small fixed set of padded shapes
    # where they outnumber eval_cache_size (KITTI-2015 has four and does
    # not need it). Must be a multiple of 8 when set; applied to the
    # KITTI validator/submission.
    eval_pad_bucket: int = 0
    # When no dataset is present on disk, the loader can serve procedurally
    # generated pairs so training/benchmarking still exercises the full path.
    synthetic_ok: bool = False
    # Procedural generator: "smooth" (dense smooth flow) or "rigid"
    # (piecewise-rigid scenes with sharp motion boundaries + occlusion —
    # the split that can separate NCUP from bilinear upsampling).
    synthetic_style: str = "smooth"


def _check_mesh_field(mesh, batch_sizes: tuple, pad_bucket: int = 0) -> None:
    """Shared (data, spatial) mesh-field validation for the
    serving and streaming configs: jit's in_shardings require every
    allowed batch size to divide the `data` axis, and under a mesh
    every pad rounds to 8*spatial, so an explicit ``pad_bucket`` must
    be a multiple of that divisor (InputPadder rejects the combination
    per call — a violation must be a clear error at config time, not
    an exception escaping FlowServer.submit() past the terminal-status
    contract)."""
    if mesh is None:
        return
    m = tuple(int(x) for x in mesh)
    if len(m) != 2 or any(x < 1 for x in m):
        raise ValueError(
            f"mesh must be two positive sizes, (data, spatial): {mesh!r}"
        )
    data, spatial = m
    bad = [b for b in batch_sizes if b % data]
    if bad:
        raise ValueError(
            f"batch sizes {bad} are not divisible by mesh data={data}; "
            "every allowed batch program shards its batch axis over the "
            "data mesh axis"
        )
    if pad_bucket and pad_bucket % (8 * spatial):
        raise ValueError(
            f"pad_bucket {pad_bucket} must be a multiple of the mesh "
            f"pad divisor 8*spatial = {8 * spatial}"
        )


@dataclass(frozen=True)
class ServeConfig:
    """Online flow-serving knobs (raft_ncup_tpu/serving/; docs/SERVING.md).

    The executable-set arithmetic the bounds below control: every
    compiled serving program is keyed by (padded shape, batch size,
    iteration level), so the steady-state program count is
    ``n_padded_shapes x len(batch_sizes) x len(iter_levels)`` —
    ``pad_bucket`` bounds the first factor, the two fixed tuples bound
    the rest, and ``cache_size`` must be at least their product or the
    LRU evicts programs the next burst re-pays (ShapeCachedForward logs
    evictions loudly).
    """

    # Admission-queue capacity: the backpressure contract. Open-loop
    # arrivals + an unbounded queue = unbounded p99; a full queue sheds
    # with an explicit retry_after_s hint instead of queueing.
    queue_capacity: int = 64
    # Allowed batch programs, ascending. A micro-batch is padded up to
    # the nearest size with zero rows so the batch dimension never
    # compiles a fresh executable mid-burst.
    batch_sizes: tuple[int, ...] = (1, 2, 4)
    # Anytime iteration budget levels, descending quality (serving/
    # budget.py). Level 0 is the idle-load quality; under burst the
    # controller walks down one level per high-water observation.
    iter_levels: tuple[int, ...] = (24, 16, 8)
    high_water: float = 0.75  # occupancy that degrades one level (fast)
    low_water: float = 0.25  # occupancy that counts toward recovery
    recover_patience: int = 4  # consecutive calm decisions to recover
    # Default per-request deadline (seconds from admission; None = no
    # deadline). Expired requests get a `timeout` response at batch
    # assembly, before any compute is spent on them.
    default_deadline_s: float | None = None
    # Shed hint when no service-time estimate exists yet.
    default_retry_after_s: float = 0.25
    # Round padded request shapes up to multiples of this bucket (0 =
    # off; must be a multiple of 8) — same knob as eval_pad_bucket, so
    # mixed native resolutions batch together and the padded-shape
    # factor of the executable set stays small.
    pad_bucket: int = 0
    # ShapeCachedForward LRU bound; >= the executable-set product above.
    cache_size: int = 16
    # DispatchThrottle in-flight bound (None = per-backend default:
    # 1 on CPU, 2 on accelerators).
    inflight: int | None = None
    # AsyncDrain queue depth (bounds device memory pinned by pulls).
    drain_depth: int = 2
    # Admission shape limits: smaller than min breaks the feature
    # pyramid; larger than max is rejected rather than compiled. The
    # ceiling is UHD (2176x3840 = 4K padded to /8): the banded Pallas
    # corr tier (ops/corr_pallas.py) keeps every pyramid level on a
    # kernel tier at that shape, and the onthefly fallback bounds the
    # working set, so a 4K request is servable rather than a
    # memory-wall crash (docs/PERF.md "Banded dispatch").
    min_image_hw: int = 16
    max_image_hw: tuple[int, int] = (2176, 3840)
    # Per-ServeConfig precision policy (docs/PRECISION.md): the server's
    # whole executable set compiles under this preset, and the policy
    # name is part of every compiled-program key, so two servers (or one
    # redeployed with a different preset) can never collide executables.
    # None (default) inherits the model's own policy — a server wrapped
    # around a bf16-configured model serves bf16 unless told otherwise.
    precision: str | None = None
    # (data, spatial) device-mesh sizes (docs/SHARDING.md): the
    # server's whole executable set compiles as SPMD programs over this
    # mesh — request batches shard over `data`, image height over
    # `spatial` (pads round up to 8*spatial so the 1/8-res feature
    # height divides the spatial axis). The mesh fingerprint rides every
    # compiled-program key. None (default) = unsharded single-device
    # serving.
    mesh: tuple[int, ...] | None = None

    def __post_init__(self) -> None:
        if self.precision is not None:
            from raft_ncup_tpu.precision import resolve_policy

            resolve_policy(self.precision)  # raises on an unknown preset
        bs = tuple(int(b) for b in self.batch_sizes)
        if not bs or any(b <= 0 for b in bs) or list(bs) != sorted(set(bs)):
            raise ValueError(
                f"batch_sizes must be ascending unique positives: {bs!r}"
            )
        _check_mesh_field(self.mesh, bs, self.pad_bucket)
        lv = tuple(int(x) for x in self.iter_levels)
        if not lv or any(x <= 0 for x in lv) or list(lv) != sorted(
            lv, reverse=True
        ) or len(set(lv)) != len(lv):
            raise ValueError(
                f"iter_levels must be strictly descending positives: {lv!r}"
            )

    @property
    def max_batch(self) -> int:
        return self.batch_sizes[-1]


@dataclass(frozen=True)
class StreamConfig:
    """Streaming video engine knobs (raft_ncup_tpu/streaming/;
    docs/STREAMING.md).

    One engine serves ONE padded frame shape: every admitted frame must
    pad (``InputPadder(mode='sintel', bucket=pad_bucket)``) to the same
    (H, W) the slot table was allocated at, so the executable set is
    exactly ``len(batch_sizes)`` programs and a stream lifecycle event
    (admission, eviction, anomaly reset, slot reuse) can never compile
    anything. ``capacity`` bounds the device slot table — the HBM
    contract: per-stream recurrent state is ``h/8 * w/8 * (2 +
    hidden_dim if carry_net)`` floats, allocated once, never grown.
    """

    # Concurrent-stream bound = slot-table size. Stream admission beyond
    # it sheds with a retry_after hint (soonest idle-expiry), it never
    # queues: a stream that cannot get a slot cannot make progress.
    capacity: int = 8
    # Native frame size the engine serves (frames whose PADDED shape
    # matches are also admitted — pad bucketing collapses near-identical
    # camera resolutions onto one slot-table shape). Any /8-padded shape
    # up to UHD (2176, 3840) is warmable: the banded corr tier keeps 4K
    # per-level lookups on-kernel (ops/corr_pallas.py; docs/PERF.md
    # "Banded dispatch").
    frame_hw: tuple[int, int] = (96, 128)
    pad_bucket: int = 0  # same semantics as ServeConfig.pad_bucket
    iters: int = 12  # fixed GRU iterations (one executable per batch size)
    # Allowed batch programs, ascending (zero-row padding up to the
    # nearest size, exactly like serving). A batch never holds two
    # frames of the SAME stream — state must flow through the slot table
    # between them — so sizes beyond `capacity` are never filled.
    batch_sizes: tuple[int, ...] = (1, 2, 4)
    # Frame admission queue bound (frames, across all streams).
    queue_capacity: int = 64
    # Warm-start staleness: a frame whose index gap to the previously
    # ADMITTED frame of its stream exceeds this warm-starts from COLD
    # (never from stale state). 1 = only strictly consecutive frames
    # may warm-start.
    max_frame_gap: int = 1
    # Idle/abandoned-stream eviction: a stream with no admitted frame
    # for this long (and nothing in flight) loses its slot.
    idle_timeout_s: float = 30.0
    # Also carry the GRU hidden state (net) across frames, not just the
    # forward-splatted flow. OFF by default: the reference's warm-start
    # carries flow only (core/utils/utils.py:28-56); net carry is an
    # extension and changes numerics vs the reference eval.
    carry_net: bool = False
    # In-graph anomaly bound: a frame whose low-res flow is non-finite
    # or exceeds this magnitude resets ITS stream's slot to cold-start
    # (batch-mates untouched).
    anomaly_max_flow: float = 1e4
    # Shed hint before any service-time estimate exists.
    default_retry_after_s: float = 0.25
    # ShapeCachedForward LRU bound; >= len(batch_sizes) (+1 when the
    # engine shares its cache with a warmstart splat program).
    cache_size: int = 8
    inflight: int | None = None  # DispatchThrottle bound (None = default)
    drain_depth: int = 2  # AsyncDrain queue depth
    # Query-chunk size of the in-graph warm-start splat
    # (ops/warmstart.forward_interpolate_jax): bounds the transient
    # distance matrix at chunk * (h/8 * w/8) * 4 bytes per stream row.
    splat_chunk: int = 1024
    # Per-engine precision policy (docs/PRECISION.md). Under the bf16
    # presets the slot table's recurrent state (prev low-res flow,
    # optional GRU net) is STORED in bf16 — halving per-stream HBM —
    # while the warm-start splat and coordinate arithmetic upcast to the
    # policy's pinned f32 coord dtype in-graph. None (default) inherits
    # the model's own policy.
    precision: str | None = None
    # (data, spatial) device-mesh sizes (docs/SHARDING.md): the
    # step programs compile as SPMD over this mesh — frame batches shard
    # over `data`, frame height over `spatial`, and the slot table
    # shards over `data` when (capacity + 1) divides it (else it
    # replicates). Frames pad to 8*spatial. None (default) = unsharded.
    mesh: tuple[int, ...] | None = None

    def __post_init__(self) -> None:
        if self.precision is not None:
            from raft_ncup_tpu.precision import resolve_policy

            resolve_policy(self.precision)  # raises on an unknown preset
        bs = tuple(int(b) for b in self.batch_sizes)
        if not bs or any(b <= 0 for b in bs) or list(bs) != sorted(set(bs)):
            raise ValueError(
                f"batch_sizes must be ascending unique positives: {bs!r}"
            )
        _check_mesh_field(self.mesh, bs, self.pad_bucket)
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1: {self.capacity}")
        if self.iters < 1:
            raise ValueError(f"iters must be >= 1: {self.iters}")
        if self.max_frame_gap < 1:
            raise ValueError(
                f"max_frame_gap must be >= 1: {self.max_frame_gap}"
            )

    @property
    def max_batch(self) -> int:
        return self.batch_sizes[-1]


def _to_jsonable(obj: Any) -> Any:
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {k: _to_jsonable(v) for k, v in dataclasses.asdict(obj).items()}
    if isinstance(obj, (list, tuple)):
        return [_to_jsonable(v) for v in obj]
    return obj


def config_to_json(cfg: Any) -> str:
    return json.dumps(_to_jsonable(cfg), indent=2, sort_keys=True)


def _from_dict(cls: type, d: dict) -> Any:
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name not in d:
            continue
        v = d[f.name]
        if f.name == "upsampler" and isinstance(v, dict):
            v = _from_dict(UpsamplerConfig, v)
        elif isinstance(v, list):
            v = tuple(tuple(x) if isinstance(x, list) else x for x in v)
        kwargs[f.name] = v
    return cls(**kwargs)


def model_config_from_json(s: str) -> ModelConfig:
    return _from_dict(ModelConfig, json.loads(s))


def small_model_config(variant: str = "raft", **overrides: Any) -> ModelConfig:
    """RAFT-small preset (reference: core/raft.py:29-33)."""
    return ModelConfig(variant=variant, small=True, **overrides)


def flagship_config(dataset: str = "sintel", **overrides: Any) -> ModelConfig:
    """The configuration every shipped reference script trains/evaluates:
    raft_nc_dbl with the NCUP upsampler (reference:
    train_raft_nc_things.sh:19-50)."""
    return ModelConfig(variant="raft_nc_dbl", dataset=dataset, **overrides)
