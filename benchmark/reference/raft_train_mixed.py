"""Plain reference of one training step of the published Sintel fine-tune
under mixed precision (github abdo-eldesokey/RAFT-NCUP ``train.py
--mixed_precision``: the ``autocast`` region of ``core/raft_nc_dbl.py`` around
fnet, cnet and the update block, NCUP outside it ``:161``; princeton-vl/RAFT
``train_mixed.sh``), as the program's ``bf16_train`` preset states it: the
forward, loss, ``jax.grad``, clip, AdamW and OneCycle of ``raft_train.py``,
one sample at a time, Python loop, no kernels, with the precision policy
written out as explicit roundings at exactly the stated points.

It imports nothing of ``raft_ncup_tpu`` (no ``precision``, no ``nn``); the
pieces it does not restate are ``reference/raft.py``'s and
``reference/raft_train.py``'s, used as they are, and the seeded weights are
theirs.

How a cast is written. Every array stays in a float32 container and every
product is a float32 product at ``Precision.HIGHEST``; "rounded to bfloat16"
is ``bf(x) = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)``, the
rounding of ``x.astype(bfloat16)`` (nearest even) that no compiler pass may
take out (a convert pair f32 -> bf16 -> f32 may be simplified away under
XLA's ``allow_excess_precision``). A product of two bfloat16 values is exact
in float32, so ``conv(bf(x), bf(w))`` at ``HIGHEST`` IS "bfloat16 operands,
float32 accumulation". ``jax.grad`` transposes ``bf`` into ``bf`` of the
cotangent: the backward takes the same casts, operand for operand.

The cast points (docs/PRECISION.md "What ``bf16_train`` states" has the same
list; the configuration file repeats it), each against the program's line:

 P1  image normalisation ``2 x / 255 - 1`` in float32
     (``models/raft.py:235-236``); rounded where the stem reads it (P2).
 P2  every convolution of fnet, cnet, the motion encoder and the flow head:
     input and kernel rounded to bfloat16, float32 accumulation, the sum
     rounded to bfloat16 once; the bias rounded to bfloat16, added, the sum
     rounded again (``nn/layers.py::Conv2d`` ``x.astype(cdt)``,
     ``kernel.astype(cdt)``, ``y + bias.astype(cdt)``; the flow head's
     thin-output form adds its taps in float32, ``conv2d``).  ``conv_m``.
 P3  instance norm (fnet) and frozen BatchNorm (cnet): the bfloat16 input
     widened, statistics and normalisation in float32, the result rounded
     to bfloat16 (``nn/layers.py::Norm`` ``x.astype(NORM_DTYPE)`` ...
     ``.astype(in_dtype)``).
 P4  elementwise work between them (ReLU, the residual add, ``tanh`` /
     ``relu`` of the context split, the GRU's sigmoid, tanh, ``r * h`` and
     ``(1 - z) * h + z * q``): bfloat16 in, each operation's result rounded
     to bfloat16 (the modules' ``dtype``; ``nn/extractor.py``,
     ``nn/update.py``, ``models/raft.py:265-266``).
 P5  a GRU gate: ``[h, inp, motion]`` and the kernel rounded to bfloat16,
     float32 accumulation over the whole width, the float32 bias added in
     float32, the pre-activation rounded to bfloat16 once
     (``nn/layers.py::SplitConv2d``: two parts, both float32 accumulators,
     added in float32; one sum here, the same sum in another order).
 P6  the correlation features and the volume are STORED in bfloat16, the one
     departure from upstream's region (which computes the correlation in
     float): features rounded (they are, by P2), all-pairs products with
     float32 accumulation, ``/ sqrt(C)`` in float32, the volume rounded to
     bfloat16; each pooled level the float32 mean of the bfloat16 level
     below, rounded (``models/raft.py:257-258``, ``ops/corr.py::
     build_corr_pyramid``).
 P7  PINNED float32: the lookup. Levels widened, coordinates, bilinear
     weights and the window sums in float32 (``ops/corr.py::corr_lookup``
     ``corr.astype(wdt)``); its float32 output is rounded only where
     ``convc1`` reads it (P2).
 P8  PINNED float32: ``coords0``, the ``coords1`` carry and the
     low-resolution flow ``coords1 - coords0``. The flow is rounded to
     bfloat16 where the motion encoder reads it (``models/raft.py:473``
     ``flow.astype(net.dtype)``); the flow head's bfloat16 delta is widened
     and added in float32 (``:480`` ``delta.astype(policy.coord_jnp)``).
 P9  PINNED float32: NCUP whole, at ``HIGHEST``: the flow as P8 leaves it,
     the hidden state widened (``models/raft.py:402``
     ``net.astype(policy.upsampler_jnp)``), the weights net, the normalized
     convolutions, ``8 x``. ``raft_train.ncup_upsample_taps`` as it is.
 P10 PINNED float32: the sequence loss; the master weights, the gradient
     (each parameter's cotangent leaves its last ``bf`` rounded to bfloat16
     and is widened: ``kernel.astype(cdt)`` transposed), its norm, torch's
     clip, AdamW's moments, decay and schedule (``raft_train.TrainReference``
     as it is).

The controls (``drop``), each this reference with ONE statement dropped, for
``readings.py --control``: ``upsampler_bf16`` (P9: NCUP's planes, confidences
and weights net rounded to bfloat16 like a compute region), ``coords_bf16``
(P8: the coordinate carry and the flow rounded every iteration),
``accumulate_bf16`` (P2 / P5 / P6: a product's partial sums, one per kernel
row, or per quarter of the input channels where the kernel has one row, are
rounded to bfloat16 and added in bfloat16).

A whole step cannot see ``accumulate_bf16``: through twelve iterations over
seeded weights it reads UNDER the gap of two sound bfloat16 computations of
the policy. What holds the accumulation is one product at a time on the same
operands (``site_products``): one site of every form of product the policy
names (``SITES``), the seed's own kernel on seeded inputs, the sum handed out
as P2 / P5 / P6 state it. Two sound computations of a site differ by the rare
rounding a float32 sum's order flips; a partial sum rounded on the way
differs in most elements.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.raft import (
    Scope, _coords, _nearest, _softplus10, _zero_stuff, corr_lookup, norm,
)
from benchmark.reference.raft_train import TrainReference, conv_taps, ncup_upsample_taps

_DN = ("NHWC", "HWIO", "NHWC")
HIGHEST = lax.Precision.HIGHEST
CONTROLS = ("upsampler_bf16", "coords_bf16", "accumulate_bf16")
ACC_PARTS = 4  # partial sums of a one-row product under ``accumulate_bf16``


def bf(x):
    """``x.astype(bfloat16)``, kept in a float32 container."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# ------------------------------------------------------------------- products


def _product(x, w, stride: int, acc_bf16: bool):
    """SAME convolution of operands ALREADY rounded, NHWC x HWIO: float32
    accumulation (the policy), or under the control ``accumulate_bf16`` its
    partial sums (one per kernel row; per ``ACC_PARTS``-th of the input
    channels where the kernel has one row) rounded to bfloat16 and added in
    bfloat16."""
    kh, kw, cin, _ = w.shape

    def conv(x, w, pad_h):
        return lax.conv_general_dilated(
            x, w, (stride, stride), (pad_h, (kw // 2, kw // 2)),
            dimension_numbers=_DN, precision=HIGHEST,
        )

    if not acc_bf16:
        return conv(x, w, (kh // 2, kh // 2))
    if kh == 1:
        edges = [round(i * cin / ACC_PARTS) for i in range(ACC_PARTS + 1)]
        parts = [
            conv(x[..., a:b], w[:, :, a:b], (0, 0)) for a, b in zip(edges, edges[1:]) if b > a
        ]
    else:
        rows = (-(-x.shape[1] // stride) - 1) * stride + 1
        xp = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (0, 0), (0, 0)))
        parts = [conv(xp[:, ky : ky + rows], w[ky : ky + 1], (0, 0)) for ky in range(kh)]
    acc = bf(parts[0])
    for part in parts[1:]:
        acc = bf(acc + bf(part))
    return acc


def conv_m(sc: Scope, x, stride: int = 1, acc_bf16: bool = False):
    """P2: ``reference/raft.py::conv`` under the policy (the kernel's size is
    its parameter's)."""
    w, b = sc.param("kernel", None, None), sc.param("bias", None, None)
    return bf(bf(_product(bf(x), bf(w), stride, acc_bf16)) + bf(b))


def norm_m(sc: Scope, x, kind: str):
    """P3."""
    return bf(norm(sc, x, kind))


def residual_block_m(sc: Scope, x, kind, stride, acc):
    y = jax.nn.relu(norm_m(sc.sub("norm1"), conv_m(sc.sub("conv1"), x, stride, acc), kind))
    y = jax.nn.relu(norm_m(sc.sub("norm2"), conv_m(sc.sub("conv2"), y, 1, acc), kind))
    if stride != 1:
        x = norm_m(sc.sub("downsample_norm"), conv_m(sc.sub("downsample_conv"), x, stride, acc), kind)
    return jax.nn.relu(bf(x + y))  # P4


def encoder_m(sc: Scope, x, kind: str, acc: bool):
    """``reference/raft.py::encoder`` under P2-P4 (ReLU of a bfloat16 value
    is exact)."""
    x = jax.nn.relu(norm_m(sc.sub("norm1"), conv_m(sc.sub("conv1"), x, 2, acc), kind))
    for i, stride in enumerate((1, 2, 2), start=1):
        x = residual_block_m(sc.sub(f"layer{i}_0"), x, kind, stride, acc)
        x = residual_block_m(sc.sub(f"layer{i}_1"), x, kind, 1, acc)
    return conv_m(sc.sub("conv2"), x, 1, acc)


def corr_pyramid_m(f1, f2, levels: int, acc_bf16: bool):
    """P6: ``reference/raft.py::corr_pyramid`` with the volume and every
    pooled level stored in bfloat16."""
    b, h, w, c = f1.shape
    a, q = bf(f1).reshape(b, h * w, c), bf(f2).reshape(b, h * w, c)
    if acc_bf16:
        step = c // ACC_PARTS
        parts = [
            jnp.einsum("bpc,bqc->bpq", a[..., c0 : c0 + step], q[..., c0 : c0 + step],
                       precision=HIGHEST)
            for c0 in range(0, c, step)
        ]
        vol = bf(parts[0])
        for part in parts[1:]:
            vol = bf(vol + bf(part))
    else:
        vol = jnp.einsum("bpc,bqc->bpq", a, q, precision=HIGHEST)
    out = [bf(vol / math.sqrt(c)).reshape(b, h * w, h, w)]
    for _ in range(levels - 1):
        v = out[-1]
        h2, w2 = v.shape[2] // 2, v.shape[3] // 2
        v = v[:, :, : h2 * 2, : w2 * 2].reshape(b, h * w, h2, 2, w2, 2)
        out.append(bf(v.mean(axis=(3, 5))))
    return out


def gate_m(sc: Scope, hx, acc_bf16: bool):
    """P5: one GRU gate's pre-activation."""
    w, b = sc.param("kernel", None, None), sc.param("bias", None, None)
    return bf(_product(bf(hx), bf(w), 1, acc_bf16) + b)


def update_block_m(sc: Scope, net, inp, corr, flow, acc: bool):
    """``reference/raft.py::update_block`` (no mask head) under P2, P4, P5,
    P8: ``net`` and ``inp`` arrive in bfloat16, ``corr`` and ``flow`` in
    float32; returns the bfloat16 hidden state and the bfloat16 delta."""
    enc = sc.sub("encoder")
    flow = bf(flow)  # P8: where the motion encoder reads it
    cor = jax.nn.relu(conv_m(enc.sub("convc1"), corr, 1, acc))
    cor = jax.nn.relu(conv_m(enc.sub("convc2"), cor, 1, acc))
    flo = jax.nn.relu(conv_m(enc.sub("convf1"), flow, 1, acc))
    flo = jax.nn.relu(conv_m(enc.sub("convf2"), flo, 1, acc))
    mot = jax.nn.relu(conv_m(enc.sub("conv"), jnp.concatenate([cor, flo], -1), 1, acc))
    x = jnp.concatenate([inp, mot, flow], -1)

    gru, h = sc.sub("gru"), net
    for tag in ("1", "2"):
        hx = jnp.concatenate([h, x], -1)
        z = bf(jax.nn.sigmoid(gate_m(gru.sub("convz" + tag), hx, acc)))
        r = bf(jax.nn.sigmoid(gate_m(gru.sub("convr" + tag), hx, acc)))
        q = bf(jnp.tanh(gate_m(gru.sub("convq" + tag), jnp.concatenate([bf(r * h), x], -1), acc)))
        h = bf(bf(bf(1.0 - z) * h) + bf(z * q))  # P4
    fh = sc.sub("flow_head")
    return h, conv_m(fh.sub("conv2"), jax.nn.relu(conv_m(fh.sub("conv1"), h, 1, acc)), 1, acc)


# ------------------------------------------- one product at a time (sites)

# site -> (kind, stride): a strided stem, a wide 3x3, a 7x7 over 2 channels,
# a 3x3 onto 2 channels (the flow head's last), a GRU gate over the whole
# width, the all-pairs volume: every form of product P2, P5 and P6 name. The
# site's name is its parameters' path; ``volume`` has none.
SITES = {
    "fnet/conv1": ("conv", 2),
    "fnet/layer1_0/conv1": ("conv", 1),
    "update_block/encoder/convf1": ("conv", 1),
    "update_block/flow_head/conv2": ("conv", 1),
    "update_block/gru/convz1": ("gate", 1),
    "volume": ("volume", 1),
}
SITE_BATCH = 2


def site_params(params: dict, site: str) -> dict:
    for key in site.split("/"):
        params = params[key]
    return params


def site_inputs(params: dict, seed: int, hw: tuple) -> dict:
    """Seeded float32 inputs of every site at ``hw`` (the 1/8 grid of the
    cell's crop), unit normal: ``{site: x}`` and for the volume the pair
    ``(fmap1, fmap2)``. Both sides round them where the policy says."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def draw(channels):
        return jnp.asarray(rng.standard_normal((SITE_BATCH, *hw, channels)), jnp.float32)

    out = {}
    for site, (kind, _) in SITES.items():
        if kind == "volume":
            width = site_params(params, "fnet/conv2")["kernel"].shape[-1]
            out[site] = (draw(width), draw(width))
        else:
            out[site] = draw(site_params(params, site)["kernel"].shape[2])
    return out


def site_products(params: dict, inputs: dict, drop: str | None = None) -> dict:
    """Every site's product as the policy states it (P2: rounded operands,
    float32 accumulation, one rounding; P5: the float32 bias added before
    it; P6: ``/ sqrt(C)`` before it), or under a control. Only
    ``accumulate_bf16`` moves a product."""
    acc = drop == "accumulate_bf16"
    out = {}
    for site, (kind, stride) in SITES.items():
        x = inputs[site]
        if kind == "volume":
            out[site] = corr_pyramid_m(x[0], x[1], 1, acc)[0]
            continue
        p = site_params(params, site)
        product = _product(bf(x), bf(p["kernel"]), stride, acc)
        out[site] = bf(product + p["bias"]) if kind == "gate" else bf(product)
    return out


# -------------------------------------------- the control ``upsampler_bf16``


def _nconv_bf16(sc: Scope, data, conf):
    """``raft_train.nconv_taps`` as a compute region would run it: operands
    rounded, float32 tap sums, every result rounded."""
    w = bf(_softplus10(sc.param("weight_p", None, None)))
    data, conf = bf(data), bf(conf)
    denom = bf(conv_taps(conf, w, HIGHEST))
    out = bf(bf(conv_taps(bf(data * conf), w, HIGHEST)) / bf(denom + 1e-20))
    return out, bf(denom / bf(w.sum(axis=(0, 1, 2))))


def _ncup_upsample_bf16(sc: Scope, flow_lr, net, up: dict):
    """``raft_train.ncup_upsample_taps`` with P9 dropped."""
    x_lr, guid = bf(_nearest(flow_lr, 2)), _nearest(net, 2)
    b, h, w, c = x_lr.shape
    s = up["scale"]
    west = sc.sub("weights_est_net")
    y = jnp.concatenate([x_lr, guid], -1)
    for i in range(len(up["weights_est_num_ch"])):
        y = jax.nn.relu(norm_m(west.sub(f"bn{i}"), conv_m(west.sub(f"conv{i}"), y), "batch"))
    conf_lr = bf(jax.nn.sigmoid(conv_m(west.sub("out"), y)))

    def fold(t):
        return _zero_stuff(t, s).transpose(0, 3, 1, 2).reshape(b * c, h * s, w * s, 1)

    d, cf = fold(x_lr), fold(conf_lr)
    net_i = sc.sub("interpolation_net")
    d, cf = _nconv_bf16(net_i.sub("nconv_in"), d, cf)
    d, cf = _nconv_bf16(net_i.sub("nconv_x2_0"), d, cf)
    d, cf = _nconv_bf16(
        net_i.sub("decoder_0"), jnp.concatenate([d, d], -1), jnp.concatenate([cf, cf], -1)
    )
    d, _ = _nconv_bf16(net_i.sub("nconv_out"), d, cf)
    return 8.0 * d.reshape(b, c, h * s, w * s).transpose(0, 2, 3, 1)


# ----------------------------------------------------------------- the step


class MixedTrainReference(TrainReference):
    """The reference training step under the ``bf16_train`` policy for one
    configuration file's ``model`` and ``train`` sections: ``raft_train.
    TrainReference`` (clip, AdamW, schedule, the loop over samples) with the
    loss of one sample computed under the cast points above. ``drop``: one
    of ``CONTROLS``, the policy with that statement dropped."""

    def __init__(self, model: dict, train: dict, drop: str | None = None):
        if drop is not None and drop not in CONTROLS:
            raise ValueError(f"no control {drop!r}: {CONTROLS}")
        if model["variant"] != "raft_nc_dbl":
            raise ValueError("the mixed reference covers raft_nc_dbl (NCUP outside the region)")
        self.drop = drop
        super().__init__(model, train)  # every float32 product at HIGHEST

    def _loss_fn(self, params, batch_stats, image1, image2, flow_gt, valid):
        ref, t = self.ref, self.t
        acc = self.drop == "accumulate_bf16"
        sc = ref._scope({"params": params, "batch_stats": batch_stats})
        i1 = 2.0 * (image1 / 255.0) - 1.0  # P1
        i2 = 2.0 * (image2 / 255.0) - 1.0
        f1, f2 = jnp.split(
            encoder_m(sc.sub("fnet"), jnp.concatenate([i1, i2], 0), "instance", acc), 2, axis=0
        )
        c = encoder_m(sc.sub("cnet"), i1, "batch", acc)
        net, inp = bf(jnp.tanh(c[..., :128])), jax.nn.relu(c[..., 128:])  # P4
        pyramid = corr_pyramid_m(f1, f2, ref.levels, acc)  # P6
        b, h, w, _ = image1.shape
        coords0 = _coords(b, h // 8, w // 8)
        mag = jnp.sqrt(jnp.sum(flow_gt**2, axis=-1))
        mask = ((valid >= 0.5) & (mag < t["max_flow"])).astype(jnp.float32)[..., None]
        n = int(t["iters"])
        round_coords = bf if self.drop == "coords_bf16" else (lambda x: x)
        upsample = _ncup_upsample_bf16 if self.drop == "upsampler_bf16" else ncup_upsample_taps

        def iteration(carry, weight):
            net, coords1 = carry
            coords1 = lax.stop_gradient(coords1)  # ``coords1.detach()``
            corr = corr_lookup(pyramid, coords1, ref.radius)  # P7
            net, delta = update_block_m(
                sc.sub("update_block"), net, inp, corr, round_coords(coords1 - coords0), acc
            )
            coords1 = round_coords(coords1 + delta)  # P8
            flow_up = upsample(sc.sub("upsampler"), round_coords(coords1 - coords0), net, ref.up)  # P9
            return (net, coords1), weight * jnp.mean(mask * jnp.abs(flow_up - flow_gt))  # P10

        weights = jnp.asarray([t["gamma"] ** (n - 1 - i) for i in range(n)], jnp.float32)
        _, terms = lax.scan(iteration, (net, coords0), weights)
        return jnp.sum(terms)

