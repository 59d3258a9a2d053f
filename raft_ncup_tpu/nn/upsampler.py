"""Final-flow upsampler registry (reference: core/upsampler.py).

The NCUP path is the paper's contribution: zero-stuff the low-res flow
onto the high-res grid, estimate per-pixel confidences from guidance
(+ data), and interpolate with the normalized-conv U-Net. The bilinear
upsampler baseline is also provided.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_ncup_tpu.config import UpsamplerConfig
from raft_ncup_tpu.nn.nconv_unet import NConvUNet
from raft_ncup_tpu.nn.weights_est import SimpleWeightsNet, UNetWeightsNet
from raft_ncup_tpu.ops.geometry import (
    adaptive_area_resize,
    bilinear_resize_align_corners,
)
from raft_ncup_tpu.ops.nconv import zero_stuff_upsample


class NConvUpsampler(nn.Module):
    """Normalized-convolution upsampler (reference: core/upsampler.py:75-210).

    Forward (shipped config: scale=4, use_data_for_guidance=True,
    channels_to_batch=True, est_on_high_res=False, use_residuals=False):

    1. zero-stuff the low-res data x4 onto the high-res grid;
    2. area-resize the guidance to the low-res grid, concat with the data,
       run the weights-estimation net (sigmoid confidences at low res);
    3. zero-stuff the confidences to high res;
    4. fold channels into the batch dim and run the NConv U-Net on
       (data, confidence).
    """

    cfg: UpsamplerConfig
    use_bn: bool = False  # BN in the weights net: sintel-configured models
    dtype: Any = None

    @nn.compact
    def __call__(
        self, x_lowres: jax.Array, guidance: jax.Array, *, train: bool = False
    ) -> jax.Array:
        cfg = self.cfg
        s = cfg.scale
        B, H, W, C = x_lowres.shape

        if cfg.est_on_high_res:
            data_for_guidance = zero_stuff_upsample(x_lowres, s, s)
            guid = bilinear_resize_align_corners(guidance, (H * s, W * s))
        else:
            data_for_guidance = x_lowres
            guid = adaptive_area_resize(guidance, (H, W))

        if cfg.weights_est_net == "binary":
            # Binary mask fallback (reference: core/upsampler.py:139-141).
            w = (data_for_guidance > 0).astype(x_lowres.dtype)
        else:
            if cfg.use_data_for_guidance:
                west_in = jnp.concatenate([data_for_guidance, guid], axis=-1)
            else:
                west_in = guid
            if cfg.weights_est_net == "simple":
                w = SimpleWeightsNet(
                    num_ch=cfg.weights_est_num_ch,
                    out_ch=C,
                    filter_sz=cfg.weights_est_filter_sz,
                    dilation=cfg.weights_est_dilation,
                    use_bn=self.use_bn,
                    dtype=self.dtype,
                    name="weights_est_net",
                )(west_in, train=train)
            elif cfg.weights_est_net == "unet":
                w = UNetWeightsNet(
                    num_ch=cfg.weights_est_num_ch,
                    out_ch=C,
                    dtype=self.dtype,
                    name="weights_est_net",
                )(west_in, train=train)
            else:
                raise ValueError(f"unknown weights_est_net: {cfg.weights_est_net!r}")

        interp = NConvUNet(
            in_ch=1 if cfg.channels_to_batch else C,
            channels_multiplier=cfg.channels_multiplier,
            num_downsampling=cfg.num_downsampling,
            encoder_filter_sz=cfg.encoder_filter_sz,
            decoder_filter_sz=cfg.decoder_filter_sz,
            out_filter_sz=cfg.out_filter_sz,
            pos_fn=cfg.pos_fn,
            use_bias=cfg.use_bias,
            data_pooling=cfg.data_pooling,
            shared_encoder=cfg.shared_encoder,
            use_double_conv=cfg.use_double_conv,
            name="interpolation_net",
        )

        oh, ow = H * s, W * s
        if cfg.channels_to_batch:
            # (B, h, w, C) -> (B*C, h, w, 1): channel c of sample b lands at
            # batch index b*C + c, matching the reference's NCHW
            # ``view(ib*ic, 1, oh, ow)`` (core/upsampler.py:168). Folded at
            # the resolution each array has, BEFORE the zero-stuffing, so
            # that the full-resolution arrays are born as planes (W on
            # lanes), the layout the tap sums read: folding after it is a
            # relayout of every plane through a (B, C)-minor tiling
            # (105 ms of a 1385 ms Sintel training step, PERF.md section
            # 6, PR 27).
            def fold(t):
                return t.transpose(0, 3, 1, 2).reshape(B * C, t.shape[1], t.shape[2], 1)

            xd = zero_stuff_upsample(fold(x_lowres), s, s)
            wd = fold(w) if cfg.est_on_high_res else zero_stuff_upsample(fold(w), s, s)
            out, _ = interp(xd, wd)
            out = out.reshape(B, C, oh, ow).transpose(0, 2, 3, 1)
        else:
            w_highres = w if cfg.est_on_high_res else zero_stuff_upsample(w, s, s)
            out, _ = interp(zero_stuff_upsample(x_lowres, s, s), w_highres)

        if cfg.use_residuals:
            x_highres = zero_stuff_upsample(x_lowres, s, s)
            out = jnp.where(x_highres > 0, x_highres, out)
        return out


class BilinearUpsampler(nn.Module):
    """align_corners=True bilinear baseline (reference:
    core/upsampler.py:213-220)."""

    cfg: UpsamplerConfig

    @nn.compact
    def __call__(
        self, x_lowres: jax.Array, guidance: jax.Array, *, train: bool = False
    ) -> jax.Array:
        B, H, W, C = x_lowres.shape
        s = self.cfg.scale
        return bilinear_resize_align_corners(x_lowres, (H * s, W * s))


def build_upsampler(
    cfg: UpsamplerConfig, dataset: str, dtype: Any = None, name: str = "upsampler"
) -> nn.Module:
    """Upsampler factory (reference: core/upsampler.py:10-72). BatchNorm in
    the weights-estimation net is enabled iff the model is configured for
    Sintel (reference: core/upsampler.py:41-42)."""
    if cfg.kind == "nconv":
        return NConvUpsampler(
            cfg, use_bn=(dataset == "sintel"), dtype=dtype, name=name
        )
    if cfg.kind == "bilinear":
        return BilinearUpsampler(cfg, name=name)
    raise ValueError(
        f"unknown upsampler kind {cfg.kind!r}: 'nconv' and 'bilinear' "
        "are built"
    )
