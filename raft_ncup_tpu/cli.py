"""Reference-compatible command-line interface.

Accepts the flag surface of the reference's argparse blocks plus its
reflective flag generator (reference: train.py:264-343,
core/utils/args.py:8-114) — including ``--final_upsampling=NConvUpsampler``
-style class-choice flags and ``"[3, 3, 1]"`` int-list values — and
resolves everything into the typed frozen configs of
``raft_ncup_tpu.config`` before any model is built (the reference instead
mutates ``args`` inside model constructors; SURVEY.md §3.4).

TPU-specific additions (not in the reference): ``--data_parallel``,
``--spatial_parallel`` mesh sizes, per-dataset root overrides, and
``--synthetic_ok`` for data-free smoke runs.
"""

from __future__ import annotations

import argparse
import ast
from typing import Optional, Sequence

from raft_ncup_tpu.utils.knobs import knob_raw

from raft_ncup_tpu.config import (
    DataConfig,
    ModelConfig,
    ServeConfig,
    StreamConfig,
    TrainConfig,
    UpsamplerConfig,
)


def str2bool(v: str) -> bool:
    if isinstance(v, bool):
        return v
    if v.lower() in ("yes", "true", "t", "y", "1"):
        return True
    if v.lower() in ("no", "false", "f", "n", "0"):
        return False
    raise argparse.ArgumentTypeError(f"boolean value expected, got {v!r}")


def str2intlist(v: str) -> tuple[int, ...]:
    """Parse the reference's quoted list syntax ``"[3, 3, 1]"``
    (reference: core/utils/args.py:174-175)."""
    out = ast.literal_eval(v)
    if not isinstance(out, (list, tuple)):
        raise argparse.ArgumentTypeError(f"int list expected, got {v!r}")
    return tuple(int(x) for x in out)


_UPSAMPLER_CLASSES = {
    # reference class names (core/upsampler.py) -> our registry kinds
    "NConvUpsampler": "nconv",
    "Bilinear": "bilinear",
}


def add_model_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--model", default="raft", help="model variant (train/eval) ")
    parser.add_argument("--small", action="store_true")
    parser.add_argument("--dropout", type=float, default=0.0)
    parser.add_argument("--mixed_precision", action="store_true")
    parser.add_argument("--precision", default=None,
                        choices=["f32", "bf16_infer", "bf16_train"],
                        help="precision-policy preset (docs/PRECISION.md): "
                        "the single dtype authority for the hot path. "
                        "'bf16_infer' for eval/serving, 'bf16_train' for "
                        "bf16-compute training with f32 master weights; "
                        "coords/metrics/upsampler stay f32 under every "
                        "preset. Overrides --mixed_precision when set.")
    parser.add_argument("--align_corners", action="store_true")
    parser.add_argument("--upsampler_bi", action="store_true",
                        help="use bilinear final upsampling")
    parser.add_argument("--freeze_raft", action="store_true")
    parser.add_argument("--load_pretrained", default=None)
    parser.add_argument("--corr_impl", default="volume",
                        choices=["volume", "onthefly", "pallas"])

    # --- reflective upsampler flags (reference: train.py:300-343)
    parser.add_argument("--final_upsampling", default="NConvUpsampler",
                        choices=sorted(_UPSAMPLER_CLASSES))
    parser.add_argument("--final_upsampling_scale", type=int, default=4)
    parser.add_argument("--final_upsampling_use_data_for_guidance",
                        type=str2bool, default=True)
    parser.add_argument("--final_upsampling_channels_to_batch",
                        type=str2bool, default=True)
    parser.add_argument("--final_upsampling_use_residuals",
                        type=str2bool, default=False)
    parser.add_argument("--final_upsampling_est_on_high_res",
                        type=str2bool, default=False)
    parser.add_argument("--interp_net", default="NConvUNet",
                        choices=["NConvUNet"])
    parser.add_argument("--interp_net_channels_multiplier", type=int, default=2)
    parser.add_argument("--interp_net_num_downsampling", type=int, default=1)
    parser.add_argument("--interp_net_data_pooling", default="conf_based",
                        choices=["conf_based", "max_pooling"])
    parser.add_argument("--interp_net_encoder_filter_sz", type=int, default=5)
    parser.add_argument("--interp_net_decoder_filter_sz", type=int, default=3)
    parser.add_argument("--interp_net_out_filter_sz", type=int, default=1)
    parser.add_argument("--interp_net_shared_encoder", type=str2bool, default=True)
    parser.add_argument("--interp_net_use_double_conv", type=str2bool, default=False)
    parser.add_argument("--interp_net_use_bias", type=str2bool, default=False)
    parser.add_argument("--interp_net_pos_fn", default="softplus")
    parser.add_argument("--weights_est_net", default="Simple",
                        choices=["Simple", "UNet"])
    parser.add_argument("--weights_est_net_num_ch", type=str2intlist,
                        default=(64, 32))
    parser.add_argument("--weights_est_net_filter_sz", type=str2intlist,
                        default=(3, 3, 1))
    parser.add_argument("--weights_est_net_dilation", type=str2intlist,
                        default=(1, 1, 1))


def add_platform_arg(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--platform", default=knob_raw("RAFT_NCUP_PLATFORM"),
        help="pin the jax platform before any device use (e.g. 'cpu' for "
        "tests and rehearsals). Unset, jax picks its default: the TPU "
        "where there is one. Env fallback: RAFT_NCUP_PLATFORM.",
    )


def apply_platform(args: argparse.Namespace) -> None:
    if getattr(args, "platform", None):
        from raft_ncup_tpu.utils.runtime import force_platform

        force_platform(args.platform)


def add_data_args(parser: argparse.ArgumentParser) -> None:
    d = DataConfig()
    parser.add_argument("--root_chairs", default=d.root_chairs)
    parser.add_argument("--root_things", default=d.root_things)
    parser.add_argument("--root_sintel", default=d.root_sintel)
    parser.add_argument("--root_kitti", default=d.root_kitti)
    parser.add_argument("--root_hd1k", default=d.root_hd1k)
    parser.add_argument("--chairs_split_file", default=d.chairs_split_file)
    parser.add_argument("--compressed_ft", action="store_true")
    parser.add_argument("--num_workers", type=int, default=4)
    parser.add_argument("--device_prefetch", type=int, default=d.device_prefetch,
                        help="device-side prefetch depth: batches staged on "
                        "device ahead of compute (>=2 hides the transfer)")
    parser.add_argument("--io_retries", type=int, default=d.io_retries,
                        help="bounded-backoff retries for failed dataset "
                        "reads before quarantining the sample "
                        "(resilience/retry.py)")
    parser.add_argument("--eval_cache_size", type=int, default=d.eval_cache_size,
                        help="LRU bound on shape-cached compiled eval "
                        "executables (inference/pipeline.py); evictions "
                        "are counted and logged")
    parser.add_argument("--eval_pad_bucket", type=int, default=d.eval_pad_bucket,
                        help="round padded eval shapes up to multiples of "
                        "this bucket (0=off: every frame padded to its own "
                        "multiple of 8; a validation pass already runs one "
                        "executable a native size) where the sizes "
                        "outnumber --eval_cache_size")
    parser.add_argument("--synthetic_ok", action="store_true",
                        help="fall back to procedural data if roots missing")
    parser.add_argument("--synthetic_style", default=d.synthetic_style,
                        choices=["smooth", "rigid"],
                        help="procedural generator for the fallback")


def str2ints(v: str) -> tuple[int, ...]:
    """Parse a bare comma list ``"24,16,8"`` (serving-tier flags)."""
    try:
        return tuple(int(x) for x in v.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(f"comma-joined ints expected: {v!r}")


def str2mesh(v: str) -> tuple[int, ...]:
    """Parse the ``--mesh DATA,SPATIAL`` device-mesh spec."""
    out = str2ints(v)
    if len(out) != 2 or any(x < 1 for x in out):
        raise argparse.ArgumentTypeError(
            f"mesh spec must be DATA,SPATIAL, two positive sizes: {v!r}"
        )
    return out


def add_mesh_arg(parser: argparse.ArgumentParser) -> None:
    """The (data x spatial) SPMD mesh flag shared by evaluate.py and
    serve.py (docs/SHARDING.md)."""
    parser.add_argument(
        "--mesh", type=str2mesh, default=None, metavar="DATA,SPATIAL",
        help="run the inference/serving stack sharded on a "
        "(data x spatial) device mesh, e.g. '1,2' (docs/SHARDING.md). "
        "Batches shard over data, image height over spatial; pads round "
        "up to 8*spatial. Default: unsharded.",
    )


def mesh_from_args(args: argparse.Namespace):
    """Build the jax Mesh named by ``--mesh`` (None when unset)."""
    spec = getattr(args, "mesh", None)
    if not spec:
        return None
    from raft_ncup_tpu.parallel.mesh import make_mesh

    data, spatial = spec
    return make_mesh(data=data, spatial=spatial)


def add_serve_args(parser: argparse.ArgumentParser) -> None:
    """Serving-tier knobs (ServeConfig; raft_ncup_tpu/serving/,
    docs/SERVING.md)."""
    d = ServeConfig()
    parser.add_argument("--queue_capacity", type=int,
                        default=d.queue_capacity,
                        help="bounded admission queue size; a full queue "
                        "sheds with an explicit retry-after hint")
    parser.add_argument("--serve_batch_sizes", type=str2ints,
                        default=d.batch_sizes,
                        help="allowed micro-batch programs, ascending "
                        "(e.g. '1,2,4'); batches pad up to the nearest "
                        "size so the executable set stays fixed")
    parser.add_argument("--iter_levels", type=str2ints,
                        default=d.iter_levels,
                        help="anytime GRU iteration budget levels, "
                        "descending quality (e.g. '24,16,8'); the "
                        "controller walks down under burst")
    parser.add_argument("--high_water", type=float, default=d.high_water,
                        help="queue occupancy that degrades the budget "
                        "one level (immediate)")
    parser.add_argument("--low_water", type=float, default=d.low_water,
                        help="occupancy counting toward budget recovery")
    parser.add_argument("--recover_patience", type=int,
                        default=d.recover_patience,
                        help="consecutive calm decisions before the "
                        "budget recovers one level (hysteresis)")
    parser.add_argument("--deadline_s", type=float,
                        default=d.default_deadline_s,
                        help="default per-request deadline in seconds "
                        "(unset = no deadline); expired requests get a "
                        "timeout response before any compute")
    parser.add_argument("--serve_pad_bucket", type=int, default=d.pad_bucket,
                        help="round padded request shapes up to multiples "
                        "of this bucket (0=off) so mixed resolutions "
                        "batch together")
    parser.add_argument("--serve_cache_size", type=int, default=d.cache_size,
                        help="compiled-executable LRU bound; keep >= "
                        "shapes x batch_sizes x iter_levels")
    parser.add_argument("--serve_precision", default=d.precision,
                        choices=["f32", "bf16_infer", "bf16_train"],
                        help="precision-policy preset the server's whole "
                        "executable set compiles under "
                        "(docs/PRECISION.md); part of every compiled-"
                        "program key. Default: inherit the model's policy")
    parser.add_argument("--trace_dir", default=None, metavar="DIR",
                        help="capture a jax.profiler trace of the replay "
                        "(after warmup, through the drain) into DIR: the "
                        "program's spans lie on the device's clock; reduce "
                        "with scripts/device_trace_report.py DIR")


def serve_config_from_args(args: argparse.Namespace) -> ServeConfig:
    return ServeConfig(
        queue_capacity=args.queue_capacity,
        batch_sizes=tuple(args.serve_batch_sizes),
        iter_levels=tuple(args.iter_levels),
        high_water=args.high_water,
        low_water=args.low_water,
        recover_patience=args.recover_patience,
        default_deadline_s=args.deadline_s,
        pad_bucket=args.serve_pad_bucket,
        cache_size=args.serve_cache_size,
        precision=args.serve_precision,
        mesh=getattr(args, "mesh", None),
    )


def add_stream_args(parser: argparse.ArgumentParser) -> None:
    """Streaming-engine knobs (StreamConfig; raft_ncup_tpu/streaming/,
    docs/STREAMING.md)."""
    d = StreamConfig()
    parser.add_argument("--stream_capacity", type=int, default=d.capacity,
                        help="slot-table size = concurrent-stream bound; "
                        "admission beyond it sheds with a retry hint")
    parser.add_argument("--stream_batch_sizes", type=str2ints,
                        default=d.batch_sizes,
                        help="allowed step programs, ascending (e.g. "
                        "'1,2,4'); one executable per size, compiled at "
                        "warmup")
    parser.add_argument("--stream_iters", type=int, default=d.iters,
                        help="fixed GRU iterations per frame")
    parser.add_argument("--stream_queue_capacity", type=int,
                        default=d.queue_capacity,
                        help="bounded frame admission queue (frames, "
                        "across all streams)")
    parser.add_argument("--max_frame_gap", type=int, default=d.max_frame_gap,
                        help="frame-index gap beyond which warm state is "
                        "stale and the frame cold-starts")
    parser.add_argument("--idle_timeout_s", type=float,
                        default=d.idle_timeout_s,
                        help="idle/abandoned streams lose their slot "
                        "after this long with nothing in flight")
    parser.add_argument("--carry_net", type=str2bool, nargs="?",
                        const=True, default=d.carry_net,
                        help="also carry the GRU hidden state across "
                        "frames (extension beyond the reference's "
                        "flow-only warm start)")
    parser.add_argument("--anomaly_max_flow", type=float,
                        default=d.anomaly_max_flow,
                        help="in-graph divergence bound: low-res flow "
                        "beyond this resets the stream to cold start")
    parser.add_argument("--stream_pad_bucket", type=int,
                        default=d.pad_bucket,
                        help="round padded frame shapes up to multiples "
                        "of this bucket (0=off)")
    parser.add_argument("--stream_precision", default=d.precision,
                        choices=["f32", "bf16_infer", "bf16_train"],
                        help="precision-policy preset for the engine's "
                        "step programs AND the slot-table state dtype "
                        "(bf16 halves per-stream HBM; docs/PRECISION.md). "
                        "Default: inherit the model's policy")


def stream_config_from_args(
    args: argparse.Namespace, frame_hw: tuple[int, int]
) -> StreamConfig:
    return StreamConfig(
        capacity=args.stream_capacity,
        frame_hw=tuple(frame_hw),
        pad_bucket=args.stream_pad_bucket,
        iters=args.stream_iters,
        batch_sizes=tuple(args.stream_batch_sizes),
        queue_capacity=args.stream_queue_capacity,
        max_frame_gap=args.max_frame_gap,
        idle_timeout_s=args.idle_timeout_s,
        carry_net=args.carry_net,
        anomaly_max_flow=args.anomaly_max_flow,
        precision=args.stream_precision,
        mesh=getattr(args, "mesh", None),
    )


def add_train_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--name", default="raft")
    parser.add_argument("--stage", required=True,
                        choices=["chairs", "things", "sintel", "kitti"])
    parser.add_argument("--restore_ckpt", default=None)
    parser.add_argument("--validation", type=str, nargs="+", default=[])
    parser.add_argument("--lr", type=float, default=0.00002)
    parser.add_argument("--num_steps", type=int, default=100000)
    parser.add_argument("--batch_size", type=int, default=6)
    parser.add_argument("--image_size", type=int, nargs="+",
                        default=[384, 512])
    parser.add_argument("--gpus", type=int, nargs="+", default=None,
                        help="accepted for reference-script compatibility; "
                        "ignored (device mesh comes from --data_parallel)")
    parser.add_argument("--iters", type=int, default=12)
    parser.add_argument("--wdecay", type=float, default=0.00005)
    parser.add_argument("--epsilon", type=float, default=1e-8)
    parser.add_argument("--clip", type=float, default=1.0)
    parser.add_argument("--add_noise", action="store_true")
    parser.add_argument("--gamma", type=float, default=0.8)
    parser.add_argument("--optimizer", default="adamw", type=str.lower)
    parser.add_argument("--scheduler", default="cyclic")
    parser.add_argument("--scheduler_step", type=int, default=20000)
    parser.add_argument("--val_freq", type=int, default=5000)
    parser.add_argument("--sum_freq", type=int, default=100)
    parser.add_argument("--seed", type=int, default=1234)
    parser.add_argument("--checkpoint_dir", default="checkpoints")
    parser.add_argument("--data_parallel", type=int, default=None,
                        help="data-parallel mesh size (default: all devices)")
    parser.add_argument("--spatial_parallel", type=int, default=1)
    parser.add_argument("--profile_steps", type=int, default=0,
                        help="capture a jax.profiler device trace of this "
                        "many early steps into <run_dir>/profile")
    parser.add_argument("--strict_guards", action="store_true",
                        help="assert the sync-free, recompile-free steady "
                        "state live: implicit host transfers inside the "
                        "step loop raise, and steady-state recompilation "
                        "fails the run (analysis/guards.py; docs/ANALYSIS.md)")
    # --- resilience (resilience/; docs/RESILIENCE.md) ------------------
    d = TrainConfig()
    parser.add_argument("--anomaly_sentinel", type=str2bool,
                        default=d.anomaly_sentinel,
                        help="fold the divergence sentinel into the jitted "
                        "step: non-finite loss/grad and grad-norm spikes "
                        "become skip-updates (state unchanged), counted on "
                        "device; K consecutive bad steps halt with rollback")
    parser.add_argument("--sentinel_spike_factor", type=float,
                        default=d.sentinel_spike_factor,
                        help="grad-norm above this multiple of its EMA "
                        "counts as a bad step")
    parser.add_argument("--sentinel_ema_decay", type=float,
                        default=d.sentinel_ema_decay)
    parser.add_argument("--sentinel_warmup", type=int,
                        default=d.sentinel_warmup,
                        help="good steps before spike detection arms")
    parser.add_argument("--sentinel_halt_after", type=int,
                        default=d.sentinel_halt_after,
                        help="consecutive bad steps that halt the run "
                        "(exit code 76, rollback to last good checkpoint)")
    parser.add_argument("--chaos",
                        default=knob_raw("RAFT_NCUP_CHAOS"),
                        help="deterministic fault injection for resilience "
                        "tests: comma-joined nan@STEP / ioerror@READ / "
                        "sigterm@STEP (resilience/chaos.py; env fallback "
                        "RAFT_NCUP_CHAOS)")


def model_config_from_args(
    args: argparse.Namespace, dataset: Optional[str] = None
) -> ModelConfig:
    """Resolve a ModelConfig. ``dataset`` controls upsampler BatchNorm
    (reference: core/upsampler.py:41-46) — for training it is the stage,
    for eval the --dataset flag."""
    kind = _UPSAMPLER_CLASSES[args.final_upsampling]
    if args.upsampler_bi:
        kind = "bilinear"
    ups = UpsamplerConfig(
        kind=kind,
        scale=args.final_upsampling_scale,
        use_data_for_guidance=args.final_upsampling_use_data_for_guidance,
        channels_to_batch=args.final_upsampling_channels_to_batch,
        use_residuals=args.final_upsampling_use_residuals,
        est_on_high_res=args.final_upsampling_est_on_high_res,
        channels_multiplier=args.interp_net_channels_multiplier,
        num_downsampling=args.interp_net_num_downsampling,
        encoder_filter_sz=args.interp_net_encoder_filter_sz,
        decoder_filter_sz=args.interp_net_decoder_filter_sz,
        out_filter_sz=args.interp_net_out_filter_sz,
        use_bias=args.interp_net_use_bias,
        data_pooling=args.interp_net_data_pooling,
        shared_encoder=args.interp_net_shared_encoder,
        use_double_conv=args.interp_net_use_double_conv,
        pos_fn=args.interp_net_pos_fn.lower(),
        weights_est_net=args.weights_est_net.lower(),
        weights_est_num_ch=tuple(args.weights_est_net_num_ch),
        weights_est_filter_sz=tuple(args.weights_est_net_filter_sz),
        weights_est_dilation=tuple(args.weights_est_net_dilation),
    )
    if dataset is None:
        dataset = getattr(args, "dataset", None) or getattr(args, "stage", "sintel")
    return ModelConfig(
        variant=args.model,
        small=args.small,
        dropout=args.dropout,
        # An explicit --precision (any preset, 'f32' included) wins over
        # the legacy --mixed_precision bool; only the unset default lets
        # the bool map to bf16_infer.
        precision=getattr(args, "precision", None) or "f32",
        mixed_precision=(
            args.mixed_precision
            and getattr(args, "precision", None) is None
        ),
        align_corners=args.align_corners,
        corr_impl=args.corr_impl,
        dataset=dataset,
        freeze_raft=args.freeze_raft,
        upsampler=ups,
    )


def train_config_from_args(args: argparse.Namespace) -> TrainConfig:
    size = args.image_size
    return TrainConfig(
        name=args.name,
        stage=args.stage,
        lr=args.lr,
        num_steps=args.num_steps,
        batch_size=args.batch_size,
        image_size=(size[0], size[1]),
        iters=args.iters,
        wdecay=args.wdecay,
        epsilon=args.epsilon,
        clip=args.clip,
        gamma=args.gamma,
        optimizer=args.optimizer,
        scheduler=args.scheduler,
        scheduler_step=args.scheduler_step,
        add_noise=args.add_noise,
        validation=tuple(args.validation),
        val_freq=args.val_freq,
        sum_freq=args.sum_freq,
        seed=args.seed,
        restore_ckpt=args.restore_ckpt,
        load_pretrained=args.load_pretrained,
        checkpoint_dir=args.checkpoint_dir,
        data_parallel=args.data_parallel,
        spatial_parallel=args.spatial_parallel,
        anomaly_sentinel=args.anomaly_sentinel,
        sentinel_spike_factor=args.sentinel_spike_factor,
        sentinel_ema_decay=args.sentinel_ema_decay,
        sentinel_warmup=args.sentinel_warmup,
        sentinel_halt_after=args.sentinel_halt_after,
        precision=getattr(args, "precision", None) or "f32",
    )


def data_config_from_args(args: argparse.Namespace) -> DataConfig:
    return DataConfig(
        root_chairs=args.root_chairs,
        root_things=args.root_things,
        root_sintel=args.root_sintel,
        root_kitti=args.root_kitti,
        root_hd1k=args.root_hd1k,
        chairs_split_file=args.chairs_split_file,
        compressed_ft=args.compressed_ft,
        num_workers=args.num_workers,
        device_prefetch=args.device_prefetch,
        eval_cache_size=args.eval_cache_size,
        eval_pad_bucket=args.eval_pad_bucket,
        io_retries=args.io_retries,
        synthetic_ok=args.synthetic_ok,
        synthetic_style=args.synthetic_style,
    )


def build_train_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="Train RAFT / RAFT-NCUP on TPU (JAX)"
    )
    add_train_args(parser)
    add_model_args(parser)
    add_data_args(parser)
    add_platform_arg(parser)
    return parser


def build_eval_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="Evaluate RAFT / RAFT-NCUP on TPU (JAX)"
    )
    parser.add_argument("--restore_ckpt", default=None,
                        help="orbax run dir or torch .pth")
    parser.add_argument("--dataset", required=True,
                        choices=["chairs", "sintel", "sintel_warm",
                                 "kitti"])
    parser.add_argument("--submission", action="store_true",
                        help="write leaderboard files instead of validating")
    parser.add_argument("--warm_start", action="store_true",
                        help="submission: warm-start each Sintel "
                        "sequence from the previous frame's device "
                        "forward-splat (validator analogue: --dataset "
                        "sintel_warm)")
    parser.add_argument("--write_png", action="store_true")
    parser.add_argument("--output_path", default=None)
    parser.add_argument("--export_pth", default=None, metavar="PATH",
                        help="write the loaded checkpoint as a reference-"
                             "keyed PyTorch .pth and exit")
    parser.add_argument("--spatial_parallel", type=int, default=1,
                        help="shard eval height over this many devices "
                        "(high-res inference; pairs with --corr_impl "
                        "onthefly). Shorthand for --mesh 1,N")
    add_mesh_arg(parser)
    parser.add_argument("--iters", type=int, default=None,
                        help="GRU iteration override; default keeps each "
                        "validator's reference setting (sintel 32, "
                        "chairs/kitti 24 — reference evaluate.py)")
    parser.add_argument("--batch_size", type=int, default=None,
                        help="validation batch-size override (default "
                        "keeps each validator's preset); frames group "
                        "per padded shape, short groups on shape change")
    parser.add_argument("--trace_dir", default=None, metavar="DIR",
                        help="capture a jax.profiler trace of the whole "
                        "validation or submission into DIR: the program's "
                        "spans lie on the device's clock; reduce with "
                        "scripts/device_trace_report.py DIR")
    add_model_args(parser)
    add_data_args(parser)
    add_platform_arg(parser)
    return parser


def parse_train(argv: Optional[Sequence[str]] = None):
    args = build_train_parser().parse_args(argv)
    apply_platform(args)
    model_cfg = model_config_from_args(args, dataset=args.stage)
    return args, model_cfg, train_config_from_args(args), data_config_from_args(args)


def parse_eval(argv: Optional[Sequence[str]] = None):
    args = build_eval_parser().parse_args(argv)
    apply_platform(args)
    model_cfg = model_config_from_args(args, dataset=args.dataset)
    return args, model_cfg, data_config_from_args(args)
