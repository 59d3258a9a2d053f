"""Plain reference of the TEST-MODE forward of ``raft_nc_dbl`` under mixed
precision (github abdo-eldesokey/RAFT-NCUP ``evaluate.py --mixed_precision``:
princeton-vl/RAFT's ``autocast`` around fnet, cnet and the update block,
``core/raft.py:100-112``, NCUP outside the region, ``core/raft_nc_dbl.py:
161``), as the program's ``bf16_infer`` preset states it: ``reference/raft.py``'s
three pieces (encode, one iteration, upsample) and its Python loop over the
iterations, NCUP once after the loop, with the precision policy written out
as explicit roundings at exactly the stated points.

It imports nothing of ``raft_ncup_tpu``. The rounding helper and the layer
functions under the policy are ``raft_train_mixed.py``'s, used as they are
(the same modules run in both phases); the float32 pieces it does not restate
are ``reference/raft.py``'s, and the seeded weights are theirs. How a cast is
written is said there: float32 containers, every product at ``HIGHEST``,
"rounded to bfloat16" an explicit ``lax.reduce_precision``.

The cast points (docs/PRECISION.md "What ``bf16_infer`` states" has the same
list; the configuration file repeats it), numbered as ``bf16_train``'s are,
each against the program's line:

 P1  image normalisation ``2 x / 255 - 1`` in float32
     (``models/raft.py::_encode``); rounded where the stem reads it (P2).
 P2  every convolution of fnet, cnet, the motion encoder and the flow head:
     input and kernel rounded, float32 accumulation, the sum rounded once;
     the bias rounded, added, the sum rounded (``nn/layers.py::Conv2d``; the
     flow head's thin-output form adds its taps in float32, ``conv2d``).
 P3  instance norm (fnet) and frozen BatchNorm (cnet): input widened,
     statistics and normalisation in float32, the result rounded (``Norm``).
 P4  elementwise work between them (ReLU, residual add, ``tanh`` / ``relu``
     of the context split, the GRU's sigmoid, tanh, ``r * h``,
     ``(1 - z) * h + z * q``): bfloat16 in and out.
 P5  a GRU gate: ``[h, inp, motion]`` and the kernel rounded, float32
     accumulation over the whole width, the float32 bias added in float32,
     the pre-activation rounded once. The program computes the context
     features' part ONCE per pair, before the loop (``raft.gru_context``,
     ``nn/layers.py::SplitConv2d``), keeps it as a float32 accumulator and
     adds the step's part to it in float32: one sum here, the same sum in
     another order.
 P6  THE DEPARTURE from upstream's region: the correlation features, the
     all-pairs volume and every pooled level are STORED in bfloat16
     (``policy.corr_jnp``; ``ops/corr.py::build_corr_pyramid``): float32
     accumulation, ``/ sqrt(C)`` in float32, rounded; each pooled level the
     float32 mean of the level below, rounded.
 P7  PINNED float32 at ``HIGHEST``: the lookup. Levels widened, coordinates,
     bilinear weights and window sums in float32 (``ops/corr.py::
     corr_lookup``); rounded only where ``convc1`` reads the result (P2).
 P8  PINNED float32: ``coords0``, ``coords1`` and the low-resolution flow.
     THE LOOP'S CARRY is ``(net: bfloat16, coords1: float32)``
     (``models/raft.py::_make_step``): the flow is rounded where the motion
     encoder reads it, the flow head's bfloat16 delta is widened and added in
     float32. (Early exit's convergence norm is taken in the same float32;
     this cell sets no tolerance and runs all iterations.)
 P9  PINNED float32 at ``HIGHEST``: NCUP whole, ONCE after the loop (test
     mode): the flow as P8 leaves it, the hidden state widened
     (``net.astype(policy.upsampler_jnp)``), the weights net, the normalized
     convolutions, ``8 x``. ``reference/raft.py::ncup_upsample`` as it is.
 P10 (in the training step's P10's place; there is no loss, gradient or
     optimizer here) PINNED float32: the full-resolution flow handed out
     (``policy.output_jnp``) and the metric head behind it: the crop of the
     padding, the endpoint error and the accumulator's sums
     (``inference/metrics.py``, ``policy.acc_jnp``).
 Not covered: ``policy.state_jnp`` (the streaming slot table's rows): no
     path of this cell stores one.

The controls (``drop``), each this reference with ONE statement dropped:
``coords_bf16`` (P8: the coordinate carry, the flow and the delta's addition
rounded every iteration), ``upsampler_bf16`` (P9: NCUP's planes, confidences
and weights net rounded like a compute region), ``lookup_bf16`` (P7: the
bilinear weights, each product and the window sums rounded to bfloat16),
``accumulate_bf16`` (P2 / P5 / P6: a product's partial sums rounded to
bfloat16 and added in bfloat16, as ``raft_train_mixed`` has it).

What a whole forward can see of them is the gap of the full-resolution flow to
the float32 reference; what it cannot (32 iterations over seeded weights
amplify every flipped rounding, so two sound bfloat16 forwards stand as far
apart as a forward with one statement dropped stands from either) is read one
site at a time on the same operands: ``raft_train_mixed.site_products`` for
the products, :func:`lookup_site` for the lookup, :meth:`MixedInferReference.
upsample` on a given low-resolution state for NCUP.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.raft import (
    Reference, _coords, corr_lookup, ncup_upsample, pad_sintel,
)
from benchmark.reference.raft_train_mixed import (
    _ncup_upsample_bf16, bf, corr_pyramid_m, encoder_m, update_block_m,
)

CONTROLS = ("coords_bf16", "upsampler_bf16", "lookup_bf16", "accumulate_bf16")


# ----------------------------------------------- the control ``lookup_bf16``


def _bilinear_zero_bf16(img, x, y):
    """``reference/raft.py::bilinear_zero`` with P7 dropped: the four
    weights, each product and the running sum rounded to bfloat16."""
    n, h, w = img.shape
    x0, y0 = jnp.floor(x), jnp.floor(y)
    flat = img.reshape(n, h * w)
    out = jnp.zeros(x.shape, jnp.float32)
    for dx in (0, 1):
        for dy in (0, 1):
            xi, yi = x0 + dx, y0 + dy
            wgt = bf(bf(1.0 - jnp.abs(x - xi)) * bf(1.0 - jnp.abs(y - yi)))
            ok = (xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1)
            idx = (
                jnp.clip(yi, 0, h - 1).astype(jnp.int32) * w
                + jnp.clip(xi, 0, w - 1).astype(jnp.int32)
            )
            val = jnp.take_along_axis(flat, idx.reshape(n, -1), axis=1)
            out = bf(out + bf(jnp.where(ok, wgt, 0.0) * val.reshape(x.shape)))
    return out


def corr_lookup_bf16(pyramid, coords, radius):
    """``reference/raft.py::corr_lookup`` over :func:`_bilinear_zero_bf16`."""
    b, h, w, _ = coords.shape
    k = 2 * radius + 1
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    off_x, off_y = d[:, None] * jnp.ones((1, k)), d[None, :] * jnp.ones((k, 1))
    out = []
    for lvl, vol in enumerate(pyramid):
        cx = coords[..., 0].reshape(b * h * w, 1, 1) / 2**lvl + off_x
        cy = coords[..., 1].reshape(b * h * w, 1, 1) / 2**lvl + off_y
        v = vol.reshape(b * h * w, vol.shape[2], vol.shape[3])
        out.append(_bilinear_zero_bf16(v, cx, cy).reshape(b, h, w, k * k))
    return jnp.concatenate(out, axis=-1)


def lookup_site_inputs(seed: int, hw: tuple, width: int = 256) -> tuple:
    """Seeded operands of one lookup at the grid ``hw``: two unit-normal
    feature maps and query coordinates a few cells off the identity."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x6C6F6F6B]))
    f1, f2 = (jnp.asarray(rng.standard_normal((1, *hw, width)), jnp.float32) for _ in range(2))
    shift = jnp.asarray(rng.uniform(-6.0, 6.0, (1, *hw, 2)), jnp.float32)
    return f1, f2, _coords(1, *hw) + shift


def lookup_site(f1, f2, coords, levels: int, radius: int, drop: str | None = None):
    """P6 then P7 at one site: the bfloat16 pyramid of two feature maps and
    its float32 lookup at ``coords`` (under ``lookup_bf16`` the narrowed
    lookup)."""
    pyramid = corr_pyramid_m(f1, f2, levels, False)
    lookup = corr_lookup_bf16 if drop == "lookup_bf16" else corr_lookup
    return lookup(pyramid, coords, radius)


# -------------------------------------------------------------- the forward


class MixedInferReference:
    """The reference test-mode forward under the ``bf16_infer`` policy for one
    configuration file's ``model`` section: ``reference/raft.py::Reference``'s
    three jitted pieces and Python loop, each piece under the cast points
    above. ``drop``: one of ``CONTROLS``, the policy with that statement
    dropped."""

    def __init__(self, model: dict, drop: str | None = None):
        if drop is not None and drop not in CONTROLS:
            raise ValueError(f"no control {drop!r}: {CONTROLS}")
        if model["variant"] != "raft_nc_dbl":
            raise ValueError("the mixed reference covers raft_nc_dbl (NCUP outside the region)")
        self.drop = drop
        self.ref = Reference(model)  # its float32 pieces at HIGHEST, its scope
        self._encode = jax.jit(self._encode_fn)
        self._step = jax.jit(self._step_fn)
        self.upsample = jax.jit(self._upsample_fn)

    def _round_coords(self, x):
        return bf(x) if self.drop == "coords_bf16" else x

    def _encode_fn(self, variables, image1, image2):
        ref, acc = self.ref, self.drop == "accumulate_bf16"
        sc = ref._scope(variables)
        i1 = 2.0 * (image1 / 255.0) - 1.0  # P1
        i2 = 2.0 * (image2 / 255.0) - 1.0
        f1, f2 = jnp.split(
            encoder_m(sc.sub("fnet"), jnp.concatenate([i1, i2], 0), "instance", acc), 2, axis=0
        )
        c = encoder_m(sc.sub("cnet"), i1, "batch", acc)
        net, inp = bf(jnp.tanh(c[..., :128])), jax.nn.relu(c[..., 128:])  # P4
        return tuple(corr_pyramid_m(f1, f2, ref.levels, acc)), net, inp  # P6

    def _step_fn(self, variables, pyramid, net, inp, coords1):
        ref = self.ref
        b, h, w, _ = coords1.shape
        coords0 = _coords(b, h, w)
        lookup = corr_lookup_bf16 if self.drop == "lookup_bf16" else corr_lookup
        corr = lookup(pyramid, coords1, ref.radius)  # P7
        net, delta = update_block_m(
            ref._scope(variables).sub("update_block"), net, inp, corr,
            self._round_coords(coords1 - coords0), self.drop == "accumulate_bf16",
        )
        return net, self._round_coords(coords1 + delta)  # P8: the carry

    def _upsample_fn(self, variables, net, coords1):
        """P9 on a given low-resolution state (``net`` bfloat16 values in a
        float32 container, ``coords1`` float32)."""
        ref = self.ref
        b, h, w, _ = coords1.shape
        flow_lr = self._round_coords(coords1 - _coords(b, h, w))
        ncup = _ncup_upsample_bf16 if self.drop == "upsampler_bf16" else ncup_upsample
        return ncup(ref._scope(variables).sub("upsampler"), flow_lr, net, ref.up)

    def state(self, variables, image1, image2, iters: int) -> tuple:
        """``(net, coords1)`` after ``iters`` iterations: what NCUP reads."""
        image1 = jnp.asarray(image1, jnp.float32)
        image2 = jnp.asarray(image2, jnp.float32)
        pyramid, net, inp = self._encode(variables, image1, image2)
        b, h, w, _ = image1.shape
        coords1 = _coords(b, h // 8, w // 8)
        for _ in range(iters):
            net, coords1 = self._step(variables, pyramid, net, inp, coords1)
        return net, coords1

    def flow(self, variables, image1, image2, iters: int) -> jax.Array:
        """Full-resolution flow for NHWC float32 images in [0, 255] whose
        height and width divide by 8."""
        return self.upsample(variables, *self.state(variables, image1, image2, iters))


def mixed_reference_flow(ref: MixedInferReference, variables, image1, image2, iters: int):
    """``reference/raft.py::reference_flow`` for the mixed reference: the
    native-shape (H, W, 2) flow of one unpadded pair, and the low-resolution
    state ``(net, coords1)`` it was upsampled from."""
    h, w = image1.shape[:2]
    p1, (top, left) = pad_sintel(np.asarray(image1, np.float32))
    p2, _ = pad_sintel(np.asarray(image2, np.float32))
    net, coords1 = ref.state(variables, p1[None], p2[None], iters)
    up = ref.upsample(variables, net, coords1)
    return np.asarray(jax.device_get(up))[0, top : top + h, left : left + w], (net, coords1)
