"""Plain reference for the KITTI-configured RAFT-NCUP (``model.dataset:
"kitti"``) and for upstream's KITTI numbers: ``reference/raft.py``'s forward
(float32, every product at ``Precision.HIGHEST``, a Python loop over the
iterations, one pair at a time) with the two things that differ for KITTI,
and ``validate_kitti``'s arithmetic in plain numpy.

- **No BatchNorm in the weights-estimation net.** Upstream builds NCUP's
  ``Simple`` weights net with BatchNorm for Sintel-configured models only
  (core/upsampler.py:41-46); ``raft.py``'s ``ncup_upsample`` applies it
  always (``raft.py:316``). ``ncup_upsample_kitti`` is that function with the
  normalisation left out: conv, ReLU, conv, ReLU, 1x1 conv, sigmoid. Run in
  create mode (``init_variables``) it makes a tree without the ``bn*`` nodes,
  which is the checkpoint layout the KITTI-configured program loads.
- **KITTI's padding.** Upstream's ``InputPadder(mode='kitti')`` stores
  ``[wl, wr, 0, pad_ht]`` and hands it to ``F.pad``, whose order is (left,
  right, top, bottom): the whole vertical pad goes BELOW the frame, the
  horizontal one is centred, edges replicated (core/utils/utils.py:7-20).
  ``pad_kitti`` does that.
- **KITTI's numbers** (evaluate.py:146-182): per frame the endpoint error's
  mean over the valid pixels; over the data set the mean of those means, and
  F1 = 100 x the share, pooled over all valid pixels, of ``epe > 3 and
  epe / mag > 0.05``. ``kitti_numbers`` computes them from lists of flows.

It imports nothing of ``raft_ncup_tpu``; what it shares with ``raft.py`` is
that file's layers (the same module of the benchmark).

Departures from upstream, each on purpose: those of ``raft.py`` (the
NConvUNet's unused branch, seeded draws of the published initialisers in
place of a checkpoint), and ``valid`` here is the mask as given (>= 0.5)
where upstream reads it from the ground-truth png's third channel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import raft as base


def ncup_upsample_kitti(sc: base.Scope, flow_lr, net, up: dict):
    """``raft.ncup_upsample`` without the weights net's BatchNorm: nearest
    x2, NCUP x4, values x8 (core/raft_nc_dbl.py:107-112,161)."""
    x_lr = base._nearest(flow_lr, 2)
    guid = base._nearest(net, 2)
    b, h, w, c = x_lr.shape
    s = up["scale"]

    west = sc.sub("weights_est_net")
    y = jnp.concatenate([x_lr, guid], -1)
    for i, ch in enumerate(up["weights_est_num_ch"]):
        y = jax.nn.relu(
            base.conv(west.sub(f"conv{i}"), y, ch, up["weights_est_filter_sz"][i])
        )
    conf_lr = jax.nn.sigmoid(
        base.conv(west.sub("out"), y, c, up["weights_est_filter_sz"][-1])
    )

    def fold(t):  # channels to batch: every flow channel interpolated alone
        t = base._zero_stuff(t, s)
        return t.transpose(0, 3, 1, 2).reshape(b * c, h * s, w * s, 1)

    d, cf = fold(x_lr), fold(conf_lr)
    net_i = sc.sub("interpolation_net")
    mult = up["channels_multiplier"]
    d, cf = base.nconv(net_i.sub("nconv_in"), d, cf, mult, up["encoder_filter_sz"])
    d, cf = base.nconv(net_i.sub("nconv_x2_0"), d, cf, mult, up["encoder_filter_sz"])
    d, cf = base.nconv(
        net_i.sub("decoder_0"), jnp.concatenate([d, d], -1),
        jnp.concatenate([cf, cf], -1), mult, up["decoder_filter_sz"],
    )
    d, _ = base.nconv(net_i.sub("nconv_out"), d, cf, 1, up["out_filter_sz"])
    return 8.0 * d.reshape(b, c, h * s, w * s).transpose(0, 2, 3, 1)


class KittiReference(base.Reference):
    """The reference for a configuration whose ``model.dataset`` is
    ``"kitti"``: RAFT-NCUP with the weights net's BatchNorm left out."""

    def __init__(self, model: dict, precision: str = "highest"):
        if model.get("dataset") != "kitti" or model["variant"] != "raft_nc_dbl":
            raise ValueError(f"no KITTI reference for {model!r}")
        super().__init__(model, precision)

    def _upsample_fn(self, variables, net, mask, coords1, key=None):
        sc = self._scope(variables, key)
        b, h, w, _ = coords1.shape
        flow_lr = coords1 - base._coords(b, h, w)
        return ncup_upsample_kitti(sc.sub("upsampler"), flow_lr, net, self.up)


def pad_kitti(image: np.ndarray) -> tuple[np.ndarray, tuple[int, int]]:
    """Replicate-pad an (H, W, 3) frame to multiples of 8 as upstream's
    ``InputPadder(mode='kitti')`` does: the vertical pad below the frame,
    the horizontal one centred. Returns the padded frame and the (top, left)
    offset of the original in it (top is always 0)."""
    h, w = image.shape[:2]
    ph, pw = (-h) % 8, (-w) % 8
    left = pw // 2
    out = np.pad(image, ((0, ph), (left, pw - left), (0, 0)), mode="edge")
    return out, (0, left)


def reference_flow_kitti(
    ref: KittiReference, variables, image1, image2, iters: int
) -> np.ndarray:
    """Native-shape (H, W, 2) float32 flow of one unpadded frame pair."""
    h, w = image1.shape[:2]
    p1, (top, left) = pad_kitti(np.asarray(image1, np.float32))
    p2, _ = pad_kitti(np.asarray(image2, np.float32))
    up = ref.flow(variables, p1[None], p2[None], iters)
    return np.asarray(jax.device_get(up))[0, top : top + h, left : left + w]


def kitti_numbers(flows: list, gts: list, valids: list) -> dict:
    """Upstream's ``validate_kitti`` over lists of (H, W, 2) flows, ground
    truths and (H, W) masks, one frame at a time, float64 sums: ``epe`` the
    mean of the per-frame means over valid pixels, ``f1`` 100 x the pooled
    share of outliers. A frame without a valid pixel is upstream's NaN; the
    benchmark's pool has none."""
    epe_list, out_list = [], []
    for flow, gt, valid in zip(flows, gts, valids):
        epe = np.sqrt(((flow - gt) ** 2).sum(-1)).ravel()
        mag = np.sqrt((gt**2).sum(-1)).ravel()
        val = valid.ravel() >= 0.5
        out = (epe > 3.0) & ((epe / mag) > 0.05)
        epe_list.append(epe[val].mean())
        out_list.append(out[val])
    return {
        "epe": float(np.mean(epe_list)),
        "f1": 100.0 * float(np.mean(np.concatenate(out_list))),
    }
