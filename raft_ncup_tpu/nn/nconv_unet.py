"""Normalized-convolution U-Net (reference: core/nconv_modules.py:25-136).

A confidence-aware interpolation network: every layer is a normalized
convolution propagating (data, confidence) pairs; downsampling pools
confidence and gathers data at the confidence argmax; the decoder
nearest-upsamples and concatenates skip features.

Faithfulness note: the reference decoder indexes ``x[i + nds]`` /
``c[nds - i]`` (core/nconv_modules.py:128-131). For the shipped
``num_downsampling=1`` configs this concatenates the full-resolution
encoder output *with itself* and never consumes the downsampled branch —
the deepest encoder output is overwritten before use. We reproduce that
wiring exactly (checkpoint + behavior parity); XLA dead-code-eliminates
the unused branch, so it costs nothing.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_ncup_tpu.nn.layers import PARAM_DTYPE
from raft_ncup_tpu.ops.geometry import upsample_nearest
from raft_ncup_tpu.ops.nconv import downsample_data_conf, nconv2d, positivity
from raft_ncup_tpu.precision.sites import record_site


class NConv2dLayer(nn.Module):
    """Normalized conv layer with softplus-reparameterized weights.

    The raw parameter is named ``weight_p`` to mirror the reference's
    EnforcePos reparameterization (core/nconv_modules.py:218-242): the
    effective kernel is ``pos_fn(weight_p)``, and ``weight_p`` is
    initialized to ``pos_fn(N(2, sqrt(2/n)))`` with n = kh*kw*out_ch
    (core/nconv_modules.py:207-209 followed by EnforcePos.apply).
    """

    features: int
    kernel_size: int = 3
    pos_fn: str = "softplus"
    use_bias: bool = False
    groups: int = 1

    @nn.compact
    def __call__(
        self, data: jax.Array, conf: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        k = self.kernel_size
        cin = data.shape[-1]
        n = k * k * self.features

        def raw_init(key, shape, dtype=PARAM_DTYPE):
            w = 2.0 + math.sqrt(2.0 / n) * jax.random.normal(key, shape, dtype)
            return positivity(w, self.pos_fn)

        raw = self.param(
            "weight_p", raw_init, (k, k, cin // self.groups, self.features), PARAM_DTYPE
        )
        weight = positivity(raw, self.pos_fn)

        bias = None
        if self.use_bias:
            fan_in = (cin // self.groups) * k * k
            bound = 1.0 / math.sqrt(fan_in)

            def bias_init(key, shape, dtype=PARAM_DTYPE):
                return jax.random.uniform(key, shape, dtype, -bound, bound)

            bias = self.param("bias", bias_init, (self.features,), PARAM_DTYPE)

        record_site("/".join(self.path), data.dtype)
        return nconv2d(
            data, conf, weight, bias, groups=self.groups, propagate_conf=True
        )


class NConvUNet(nn.Module):
    """reference: core/nconv_modules.py:25-136 (constructor defaults and
    the shipped config: train_raft_nc_things.sh:37-46)."""

    in_ch: int = 1
    channels_multiplier: int = 2
    num_downsampling: int = 1
    encoder_filter_sz: int = 5
    decoder_filter_sz: int = 3
    out_filter_sz: int = 1
    pos_fn: str = "softplus"
    groups: int = 1
    use_bias: bool = False
    data_pooling: str = "conf_based"
    shared_encoder: bool = True
    use_double_conv: bool = False

    @nn.compact
    def __call__(
        self, data: jax.Array, conf: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        mult = self.in_ch * self.channels_multiplier
        nds = self.num_downsampling

        nconv_in = NConv2dLayer(
            mult, self.encoder_filter_sz, self.pos_fn, self.use_bias, self.groups,
            name="nconv_in",
        )
        n_x2 = 2 if self.use_double_conv else 1
        nconv_x2 = [
            NConv2dLayer(
                mult, self.encoder_filter_sz, self.pos_fn, self.use_bias, self.groups,
                name=f"nconv_x2_{i}",
            )
            for i in range(n_x2)
        ]
        if not self.shared_encoder:
            deep_encoders = [
                NConv2dLayer(
                    mult, self.encoder_filter_sz, self.pos_fn, self.use_bias,
                    self.groups, name=f"encoder_{i + 1}",
                )
                for i in range(nds)
            ]
        decoders = [
            NConv2dLayer(
                mult, self.decoder_filter_sz, self.pos_fn, self.use_bias, self.groups,
                name=f"decoder_{i}",
            )
            for i in range(nds)
        ]
        nconv_out = NConv2dLayer(
            self.in_ch, self.out_filter_sz, self.pos_fn, False, self.groups,
            name="nconv_out",
        )

        def enc0(d, c):
            d, c = nconv_in(d, c)
            for layer in nconv_x2:
                d, c = layer(d, c)
            return d, c

        def enc_deep(i, d, c):
            # Shared encoder reuses the first nconv_x2 layer at every scale
            # (reference: core/nconv_modules.py:77-79).
            if self.shared_encoder:
                return nconv_x2[0](d, c)
            return deep_encoders[i](d, c)

        x: list = [None] * (nds * 2 + 1)
        c: list = [None] * (nds * 2 + 1)
        x[0], c[0] = data, conf

        if nds == 0:
            x[0], c[0] = enc0(x[0], c[0])
        else:
            for i in range(nds + 1):
                if i == 0:
                    x[i + 1], c[i + 1] = enc0(x[i], c[i])
                else:
                    d_ds, c_ds = downsample_data_conf(x[i], c[i], self.data_pooling)
                    x[i + 1], c[i + 1] = enc_deep(i - 1, d_ds, c_ds)
            for i in range(nds):
                # Faithful reference indexing (see module docstring).
                target_h, target_w = c[nds - i].shape[1], c[nds - i].shape[2]
                src_h = x[i + nds].shape[1]
                factor = target_h // src_h if src_h else 1
                if factor > 1:
                    x_up = upsample_nearest(x[i + nds], factor)
                    c_up = upsample_nearest(c[i + nds], factor)
                else:
                    x_up, c_up = x[i + nds], c[i + nds]
                x[i + nds + 1], c[i + nds + 1] = decoders[i](
                    jnp.concatenate([x_up, x[nds - i]], axis=-1),
                    jnp.concatenate([c_up, c[nds - i]], axis=-1),
                )

        return nconv_out(x[-1], c[-1])
