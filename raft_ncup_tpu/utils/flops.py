"""Paper-FLOPs accounting and MFU estimation for the RAFT/NCUP models.

The reference records no FLOPs or throughput anywhere (BASELINE.md); this
module provides an analytic per-forward FLOP count from the architecture
constants (reference anchors: encoders core/extractor.py:118-192, corr
matmul core/corr.py:13-21, update block core/update.py:79-141, NCUP
core/upsampler.py:143-177 + core/nconv_modules.py:25-136) so a report can
state MFU = achieved FLOPs/s over the chip's peak. When a compiled
executable is at hand, prefer XLA's own ``cost_analysis()['flops']`` —
the cost ledger (``inference/costs.py``) banks that for every warmed
executable.

Counting convention: one conv = 2*k*k*Cin*Cout*Hout*Wout FLOPs (MAC = 2).
Elementwise/normalization work is ignored (sub-1% for these models).
"""

from __future__ import annotations

from raft_ncup_tpu.config import ModelConfig

# Peak dense-matmul FLOP/s per chip (bf16), keyed by the ``device_kind``
# string jax reports (``jax.devices()[0].device_kind``). Source: Google
# Cloud TPU documentation, the per-generation "System architecture" pages
# ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s). A chip that is
# not in this table is an error on platform ``tpu``, never a default.
TPU_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _conv(k: int, cin: int, cout: int, h: int, w: int) -> float:
    return 2.0 * k * k * cin * cout * h * w


def _basic_encoder_flops(h: int, w: int, out_dim: int) -> float:
    """BasicEncoder on one (h, w) image (reference: core/extractor.py:118-192):
    7x7/2 stem to 64, three 2-block residual stages 64(s1)/96(s2)/128(s2),
    1x1 head to ``out_dim``."""
    f = 0.0
    h2, w2 = h // 2, w // 2
    f += _conv(7, 3, 64, h2, w2)  # stem
    # layer1: two blocks at 64ch, stride 1, (h/2, w/2)
    f += 4 * _conv(3, 64, 64, h2, w2)
    # layer2: 64->96 stride 2 at (h/4, w/4) incl. 1x1 downsample shortcut
    h4, w4 = h // 4, w // 4
    f += _conv(3, 64, 96, h4, w4) + _conv(3, 96, 96, h4, w4)
    f += _conv(1, 64, 96, h4, w4)
    f += 2 * _conv(3, 96, 96, h4, w4)
    # layer3: 96->128 stride 2 at (h/8, w/8)
    h8, w8 = h // 8, w // 8
    f += _conv(3, 96, 128, h8, w8) + _conv(3, 128, 128, h8, w8)
    f += _conv(1, 96, 128, h8, w8)
    f += 2 * _conv(3, 128, 128, h8, w8)
    f += _conv(1, 128, out_dim, h8, w8)  # head
    return f


def _update_block_flops(h8: int, w8: int, corr_planes: int) -> float:
    """BasicMotionEncoder + SepConvGRU + FlowHead per iteration at 1/8 res
    (reference: core/update.py:79-141)."""
    f = 0.0
    # motion encoder
    f += _conv(1, corr_planes, 256, h8, w8)
    f += _conv(3, 256, 192, h8, w8)
    f += _conv(7, 2, 128, h8, w8)
    f += _conv(3, 128, 64, h8, w8)
    f += _conv(3, 192 + 64, 126, h8, w8)
    # SepConvGRU: two sequential GRUs (1x5 then 5x1), three k=5 separable
    # convs each, 6 per iteration, over [h, motion] = 256 channels: what
    # they make of the 128 context channels is _gru_context_flops, once.
    f += 6 * (2.0 * 5 * 256 * 128 * h8 * w8)
    # flow head
    f += _conv(3, 128, 256, h8, w8) + _conv(3, 256, 2, h8, w8)
    return f


def _gru_context_flops(h8: int, w8: int, context_dim: int, hidden_dim: int) -> float:
    """The six gate convolutions' share of the context features, once per
    pair before the refinement loop (``nn/update.py``, PR 31)."""
    return 6 * (2.0 * 5 * context_dim * hidden_dim * h8 * w8)


def _mask_head_flops(h8: int, w8: int, hidden_dim: int) -> float:
    """``BasicUpdateBlock.mask``: 3x3 hidden -> 256, 1x1 256 -> 9 x 64."""
    return _conv(3, hidden_dim, 256, h8, w8) + _conv(1, 256, 576, h8, w8)


def _ncup_flops(cfg: ModelConfig, H: int, W: int, batch_mult: int) -> float:
    """One NCUP x4 upsampling pass: Simple weights-net at the x4 LR grid
    (H/4) + NConvUNet at full res with channels_to_batch (reference:
    core/upsampler.py:143-177, core/interp_weights_est.py:10-47,
    core/nconv_modules.py:25-136)."""
    up = cfg.upsampler
    f = 0.0
    # weights estimation at the LR grid of the x4 stage = (H/4, W/4);
    # input = data(2) + guidance(128) = 130 channels.
    h4, w4 = H // 4, W // 4
    chans = (130,) + tuple(up.weights_est_num_ch) + (2,)
    for k, cin, cout in zip(up.weights_est_filter_sz, chans[:-1], chans[1:]):
        f += _conv(k, cin, cout, h4, w4)
    # NConvUNet on (B*2, 1ch) full-res maps; every NConv2d = two convs
    # (conv(c*x) and conv(c)). Shared 5x5 encoder at full + half res,
    # 3x3 decoder at full res, 1x1 head. mult = channels_multiplier.
    m = up.channels_multiplier
    ke, kd, ko = up.encoder_filter_sz, up.decoder_filter_sz, up.out_filter_sz
    f_unet = 0.0
    f_unet += 2 * _conv(ke, 1, m, H, W)  # encoder at full res
    f_unet += 2 * _conv(ke, m, m, H // 2, W // 2)  # encoder at half res
    f_unet += 2 * _conv(kd, 2 * m, m, H, W)  # decoder (skip concat)
    f_unet += 2 * _conv(ko, m, 1, H, W)  # head
    f += batch_mult * f_unet  # channels_to_batch: run per flow channel
    return f


def forward_flops(
    cfg: ModelConfig, batch: int, height: int, width: int, iters: int
) -> float:
    """Analytic FLOPs for one test-mode forward of ``cfg`` at the given
    input shape. Returns total FLOPs for the whole batch."""
    H, W = height, width
    h8, w8 = H // 8, W // 8
    f = 0.0
    f += 2 * _basic_encoder_flops(H, W, cfg.fnet_dim)  # fnet on both frames
    f += _basic_encoder_flops(H, W, cfg.hidden_dim + cfg.context_dim)  # cnet
    if cfg.corr_impl == "volume":
        # all-pairs matmul (reference: core/corr.py:47-55)
        f += 2.0 * (h8 * w8) ** 2 * cfg.fnet_dim
    else:
        # on-the-fly: per-iteration windowed dot products, L levels x K^2 taps
        K2 = (2 * cfg.resolved_corr_radius + 1) ** 2
        f += iters * cfg.corr_levels * K2 * 2.0 * h8 * w8 * cfg.fnet_dim
    f += _gru_context_flops(h8, w8, cfg.context_dim, cfg.hidden_dim)
    f += iters * _update_block_flops(h8, w8, cfg.corr_planes)
    if cfg.variant == "raft_nc_dbl":
        f += iters * _ncup_flops(cfg, H, W, batch_mult=2)
    else:
        # convex-mask head (reference: core/update.py:123-126), once, on
        # the state the loop leaves (models/raft.py::_upsample, PR 33)
        f += _mask_head_flops(h8, w8, cfg.hidden_dim)
    return batch * f


def train_step_flops(
    cfg: ModelConfig, batch: int, height: int, width: int, iters: int
) -> float:
    """Forward + backward ~= 3x forward (standard paper accounting)."""
    return 3.0 * forward_flops(cfg, batch, height, width, iters)
