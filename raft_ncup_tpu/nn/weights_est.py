"""Confidence/weights estimation networks for the NCUP upsampler
(reference: core/interp_weights_est.py)."""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from raft_ncup_tpu.nn.layers import Conv2d, ConvTranspose2d, Norm
from raft_ncup_tpu.utils.remat import WEIGHTS_NET_CONV


class SimpleWeightsNet(nn.Module):
    """Conv(+BN)+ReLU stack with a sigmoid 1x1-ish head (reference:
    core/interp_weights_est.py:10-47).

    ``num_ch`` excludes the input channel count (it is inferred from the
    input, unlike the reference which prepends it to the list). BatchNorm
    is enabled for Sintel-configured models only (reference:
    core/upsampler.py:41-46).
    """

    num_ch: tuple[int, ...] = (64, 32)
    out_ch: int = 2
    filter_sz: tuple[int, ...] = (3, 3, 1)
    dilation: tuple[int, ...] = (1, 1, 1)
    use_bn: bool = False
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = False) -> jax.Array:
        assert len(self.filter_sz) == len(self.num_ch) + 1
        for i, ch in enumerate(self.num_ch):
            k, d = self.filter_sz[i], self.dilation[i]
            pad = k // 2 + ((k - 1) * (d - 1)) // 2
            x = Conv2d(
                ch, k, dilation=d, padding=pad, dtype=self.dtype, name=f"conv{i}"
            )(x)
            # What the training step keeps of this net across its loop
            # (utils/remat.py); norm and ReLU are recomputed from it.
            x = checkpoint_name(x, WEIGHTS_NET_CONV)
            if self.use_bn:
                x = Norm("batch", name=f"bn{i}")(x, train=train)
            x = nn.relu(x)
        k, d = self.filter_sz[-1], self.dilation[-1]
        pad = k // 2 + ((k - 1) * (d - 1)) // 2
        x = Conv2d(
            self.out_ch, k, dilation=d, padding=pad, dtype=self.dtype, name="out"
        )(x)
        return nn.sigmoid(x)


class _DoubleConv(nn.Module):
    """(conv => BN => ReLU) * 2 (reference: core/interp_weights_est.py:85-100)."""

    out_ch: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = False) -> jax.Array:
        for i in range(2):
            x = Conv2d(self.out_ch, 3, dtype=self.dtype, name=f"conv{i}")(x)
            x = Norm("batch", name=f"bn{i}")(x, train=train)
            x = nn.relu(x)
        return x


class UNetWeightsNet(nn.Module):
    """Classic double-conv U-Net with ConvTranspose ups and pad-to-match
    skips (reference: core/interp_weights_est.py:50-155)."""

    num_ch: tuple[int, ...] = (16, 32, 64)
    out_ch: int = 2
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = False) -> jax.Array:
        n_down = len(self.num_ch) - 1
        feats = [
            _DoubleConv(self.num_ch[0], dtype=self.dtype, name="inconv")(
                x, train=train
            )
        ]
        for i in range(n_down):
            y = nn.max_pool(feats[-1], (2, 2), strides=(2, 2))
            feats.append(
                _DoubleConv(self.num_ch[i + 1], dtype=self.dtype, name=f"down{i}")(
                    y, train=train
                )
            )

        y = feats[-1]
        for i in range(n_down):
            skip = feats[-i - 2]
            y = ConvTranspose2d(
                y.shape[-1], 2, stride=2, dtype=self.dtype, name=f"up{i}_tconv"
            )(y)
            dh = skip.shape[1] - y.shape[1]
            dw = skip.shape[2] - y.shape[2]
            y = jnp.pad(
                y,
                (
                    (0, 0),
                    (dh // 2, dh - dh // 2),
                    (dw // 2, dw - dw // 2),
                    (0, 0),
                ),
            )
            y = _DoubleConv(
                self.num_ch[-i - 2], dtype=self.dtype, name=f"up{i}_conv"
            )(jnp.concatenate([skip, y], axis=-1), train=train)

        y = Conv2d(self.out_ch, 1, dtype=self.dtype, name="outconv")(y)
        return nn.sigmoid(y)
