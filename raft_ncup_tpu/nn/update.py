"""Recurrent update blocks (reference: core/update.py).

Motion encoder fuses correlation features and current flow; a conv GRU
(separable 1x5/5x1 for the Basic variant) refines a hidden state; a flow
head emits the per-iteration flow delta, and (for the RAFT baseline) a mask
head emits the convex-upsampling weights scaled by 0.25 (reference:
core/update.py:138-140).

These run inside ``lax.scan`` over refinement iterations, so everything is
shape-static. The GRU state is the scan carry.

The mask head reads the hidden state alone and feeds nothing back into the
recurrence, so it is a method of its own (``mask``) and not part of
``step``: a caller that upsamples only the last iteration's flow (test
mode) runs it once, after the loop, on the state the loop leaves; one that
upsamples every iteration (the training loss) calls it after every
``step`` (PERF.md section 6, PR 33).

The GRU reads ``[h, inp, motion]``, and the context features ``inp`` are
the same tensor in every iteration. What the gate convolutions make of them
is therefore computed once per pair, before the loop (``context``), and the
loop's convolutions run over ``[h, motion]`` alone (``step``): the same sum
in another order (PERF.md section 6, PR 31).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_ncup_tpu.nn.layers import Conv2d, SplitConv2d


class FlowHead(nn.Module):
    """reference: core/update.py:6-14."""

    hidden_dim: int = 256
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = Conv2d(self.hidden_dim, 3, dtype=self.dtype, name="conv1")(x)
        x = nn.relu(x)
        return Conv2d(2, 3, dtype=self.dtype, name="conv2")(x)


class _ContextGRU(nn.Module):
    """A conv GRU over ``[h, inp, motion]`` whose context features ``inp``
    do not change over the refinement loop: ``context(inp)``, once per pair,
    gives every gate's part of them, and ``__call__(h, ctx, motion)`` is the
    update the loop runs, over the channels of ``h`` and ``motion`` alone.
    ``input_dim`` is the reference's: ``inp`` and ``motion`` together.
    ``PASSES``: gate-name suffix -> kernel size, one GRU update each."""

    PASSES = {}

    hidden_dim: int = 128
    input_dim: int = 192 + 128
    context_dim: int = 128
    dtype: Any = None

    def setup(self):
        for suffix, kernel_size in self.PASSES.items():
            for g in "zrq":
                setattr(self, f"conv{g}{suffix}", SplitConv2d(
                    self.hidden_dim, kernel_size, self.hidden_dim + self.input_dim,
                    (self.hidden_dim, self.hidden_dim + self.context_dim),
                    dtype=self.dtype,
                ))

    def _gates(self, suffix: str) -> tuple:
        return tuple(getattr(self, f"conv{g}{suffix}") for g in "zrq")

    def context(self, inp: jax.Array) -> dict:
        return {
            gate.name: gate.context(inp)
            for suffix in self.PASSES for gate in self._gates(suffix)
        }

    def __call__(self, h: jax.Array, ctx: dict, motion: jax.Array) -> jax.Array:
        for suffix in self.PASSES:
            convz, convr, convq = self._gates(suffix)
            hm = jnp.concatenate([h, motion], axis=-1)
            z = nn.sigmoid(convz(hm, ctx[convz.name]))
            r = nn.sigmoid(convr(hm, ctx[convr.name]))
            q = nn.tanh(
                convq(jnp.concatenate([r * h, motion], axis=-1), ctx[convq.name])
            )
            h = (1 - z) * h + z * q
        return h


class ConvGRU(_ContextGRU):
    """Plain 3x3 conv GRU (reference: core/update.py:16-31)."""

    PASSES = {"": 3}


class SepConvGRU(_ContextGRU):
    """Separable GRU: a horizontal (1x5) pass then a vertical (5x1) pass
    (reference: core/update.py:33-60)."""

    PASSES = {"1": (1, 5), "2": (5, 1)}


class SmallMotionEncoder(nn.Module):
    """reference: core/update.py:62-77."""

    corr_planes: int
    dtype: Any = None

    @nn.compact
    def __call__(self, flow: jax.Array, corr: jax.Array) -> jax.Array:
        cor = nn.relu(Conv2d(96, 1, dtype=self.dtype, name="convc1")(corr))
        flo = nn.relu(Conv2d(64, 7, dtype=self.dtype, name="convf1")(flow))
        flo = nn.relu(Conv2d(32, 3, dtype=self.dtype, name="convf2")(flo))
        out = nn.relu(
            Conv2d(80, 3, dtype=self.dtype, name="conv")(
                jnp.concatenate([cor, flo], axis=-1)
            )
        )
        return jnp.concatenate([out, flow], axis=-1)


class BasicMotionEncoder(nn.Module):
    """reference: core/update.py:79-97."""

    corr_planes: int
    dtype: Any = None

    @nn.compact
    def __call__(self, flow: jax.Array, corr: jax.Array) -> jax.Array:
        cor = nn.relu(Conv2d(256, 1, dtype=self.dtype, name="convc1")(corr))
        cor = nn.relu(Conv2d(192, 3, dtype=self.dtype, name="convc2")(cor))
        flo = nn.relu(Conv2d(128, 7, dtype=self.dtype, name="convf1")(flow))
        flo = nn.relu(Conv2d(64, 3, dtype=self.dtype, name="convf2")(flo))
        out = nn.relu(
            Conv2d(128 - 2, 3, dtype=self.dtype, name="conv")(
                jnp.concatenate([cor, flo], axis=-1)
            )
        )
        return jnp.concatenate([out, flow], axis=-1)


class _UpdateBlock(nn.Module):
    """What the two update blocks share: ``context(inp)`` once per pair,
    ``step(net, ctx, corr, flow) -> (net, delta)`` in every refinement
    iteration, ``mask(net)`` wherever a prediction is upsampled (``None``
    without a mask head), and ``__call__(net, inp, corr, flow) -> (net,
    mask, delta)``, the three in a row (one iteration on its own;
    ``init``)."""

    def context(self, inp: jax.Array) -> dict:
        """The GRU gates' share of the context features (and the kernel
        rows of the rest): everything of the block that reads ``inp``."""
        return self.gru.context(inp)

    def step(
        self, net: jax.Array, ctx: dict, corr: jax.Array, flow: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        net = self.gru(net, ctx, self.encoder(flow, corr))
        return net, self.flow_head(net)

    def mask(self, net: jax.Array) -> Optional[jax.Array]:
        return None

    def __call__(
        self, net: jax.Array, inp: jax.Array, corr: jax.Array, flow: jax.Array
    ) -> tuple[jax.Array, Optional[jax.Array], jax.Array]:
        net, delta = self.step(net, self.context(inp), corr, flow)
        return net, self.mask(net), delta


class SmallUpdateBlock(_UpdateBlock):
    """reference: core/update.py:99-112. No mask head: the small path
    upsamples bilinearly."""

    corr_planes: int
    hidden_dim: int = 96
    context_dim: int = 64
    dtype: Any = None

    def setup(self):
        self.encoder = SmallMotionEncoder(self.corr_planes, dtype=self.dtype)
        self.gru = ConvGRU(
            self.hidden_dim, 82 + self.context_dim, self.context_dim, dtype=self.dtype
        )
        self.flow_head = FlowHead(128, dtype=self.dtype)


class BasicUpdateBlock(_UpdateBlock):
    """reference: core/update.py:114-141.

    ``use_mask_head=False`` reproduces raft_nc_dbl's deletion of the convex
    mask head (reference: core/raft_nc_dbl.py:68) — the NCUP upsampler
    consumes the GRU hidden state as guidance instead.
    """

    corr_planes: int
    hidden_dim: int = 128
    context_dim: int = 128
    use_mask_head: bool = True
    dtype: Any = None

    def setup(self):
        self.encoder = BasicMotionEncoder(self.corr_planes, dtype=self.dtype)
        self.gru = SepConvGRU(
            self.hidden_dim, 128 + self.context_dim, self.context_dim, dtype=self.dtype
        )
        self.flow_head = FlowHead(256, dtype=self.dtype)
        if self.use_mask_head:
            self.mask_conv1 = Conv2d(256, 3, dtype=self.dtype)
            self.mask_conv2 = Conv2d(64 * 9, 1, dtype=self.dtype)

    def mask(self, net: jax.Array) -> Optional[jax.Array]:
        """The convex-upsampling weights of the hidden state ``net``."""
        if not self.use_mask_head:
            return None
        # 0.25 scale to balance gradients (reference: core/update.py:140).
        return 0.25 * self.mask_conv2(nn.relu(self.mask_conv1(net)))
