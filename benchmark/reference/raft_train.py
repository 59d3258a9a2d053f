"""Plain reference of one training step of the published Sintel fine-tune
(github abdo-eldesokey/RAFT-NCUP ``train.py:42-71,83-99,196-224`` with
``train_raft_nc_sintel.sh:5-19``): the forward of ``reference/raft.py`` in
training mode over all refinement iterations with the upsampler on every
one, the sequence loss, ``jax.grad`` of it, the global-norm clip, AdamW and
the OneCycle schedule. Straightforward ``jax.numpy``, float32, every product
at ``Precision.HIGHEST`` (``precision="high"`` is the cell's control), no
kernels, and ``jax.checkpoint`` in one place, ``conv_taps``, to fit.

It imports nothing of ``raft_ncup_tpu``; the layers and the seeded weights
are ``reference/raft.py``'s.

What is computed, and where it departs from running the published code on
a batch, each on purpose:

- The batch is taken ONE SAMPLE AT A TIME and the losses and gradients are
  averaged. That is the same arithmetic: the loss is a mean over a batch of
  equal-sized samples, the feature encoder's instance norm is per sample,
  and every BatchNorm (context encoder, the upsampler's weights net) is
  frozen at its running statistics in this recipe (``train.py:185-186``:
  ``freeze_bn()`` for every stage but chairs, which walks all modules).
- The iterations are a ``lax.scan`` and the upsampler's normalized
  convolutions are sums over the kernel's taps (below): the same sums as the
  published convolutions, in a form whose gradient the chip's compiler can
  compile. Unrolled, or with the convolution's own kernel transpose, one
  sample's program did not compile in the chip machine's 40 GiB of host
  memory.
- Dropout is 0 and ``--add_noise`` is off in the recipe: neither is built.
- AdamW as torch computes it (decay ``p *= 1 - lr * wd``, then the Adam
  step with ``sqrt(v) / sqrt(1 - b2^t) + eps``); the clip as
  ``torch.nn.utils.clip_grad_norm_`` (coefficient ``max_norm / (norm +
  1e-6)``, never above 1); OneCycleLR with ``anneal_strategy='linear'``,
  ``pct_start=0.05``, ``cycle_momentum=False`` over ``num_steps + 100``,
  evaluated in Python floats.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.raft import (
    Reference, Scope, _coords, _nearest, _softplus10, _zero_stuff, conv, norm,
)


# ------------------------------------------- the upsampler, differentiable
#
# ``reference/raft.py``'s upsampler, layer for layer and parameter for
# parameter, with each normalized convolution's two convolutions written
# as a sum over the kernel's taps instead of ``lax.conv_general_dilated``.
# The forward is the same sum; what differs is what ``jax.grad`` makes of
# it. The transpose of a convolution with respect to its kernel is a
# convolution whose window is the whole 368x768 plane, and the TPU
# compiler needed over 19 GB of host memory to compile ONE upsampler's
# backward in that form at float32 `highest` (PERF.md section 6, PR 26);
# the transpose of a tap sum is one small contraction per tap.


@functools.partial(jax.checkpoint, static_argnums=2)
def conv_taps(x, w, precision):
    """SAME convolution, stride 1, NHWC x HWIO, odd kernel, tap by tap.
    ``jax.checkpoint``, needed to fit: the backward cuts the k*k shifted
    windows out of ``x`` again instead of keeping every one of them from
    the forward (kept, one sample's twelve iterations asked the chip for
    12.5 GiB)."""
    k = w.shape[0]
    h, wd = x.shape[1], x.shape[2]
    xp = jnp.pad(x, ((0, 0), (k // 2, k // 2), (k // 2, k // 2), (0, 0)))
    out = 0.0
    for ky in range(k):
        for kx in range(k):
            out = out + jnp.einsum(
                "bhwc,co->bhwo", xp[:, ky : ky + h, kx : kx + wd], w[ky, kx],
                precision=precision,
            )
    return out


def nconv_taps(sc: Scope, data, conf, features, k):
    """``reference/raft.py::nconv`` with ``conv_taps``."""
    raw = sc.param("weight_p", (k, k, data.shape[-1], features), None)
    w = _softplus10(raw)
    denom = conv_taps(conf, w, sc.precision)
    out = conv_taps(data * conf, w, sc.precision) / (denom + 1e-20)
    return out, denom / w.sum(axis=(0, 1, 2))


def ncup_upsample_taps(sc: Scope, flow_lr, net, up: dict):
    """``reference/raft.py::ncup_upsample`` with ``nconv_taps``: nearest
    x2, NCUP x4, values x8."""
    x_lr, guid = _nearest(flow_lr, 2), _nearest(net, 2)
    b, h, w, c = x_lr.shape
    s = up["scale"]
    west = sc.sub("weights_est_net")
    y = jnp.concatenate([x_lr, guid], -1)
    for i, ch in enumerate(up["weights_est_num_ch"]):
        y = conv(west.sub(f"conv{i}"), y, ch, up["weights_est_filter_sz"][i])
        y = jax.nn.relu(norm(west.sub(f"bn{i}"), y, "batch"))
    conf_lr = jax.nn.sigmoid(conv(west.sub("out"), y, c, up["weights_est_filter_sz"][-1]))

    def fold(t):  # channels to batch: every flow channel is interpolated alone
        return _zero_stuff(t, s).transpose(0, 3, 1, 2).reshape(b * c, h * s, w * s, 1)

    d, cf = fold(x_lr), fold(conf_lr)
    net_i, mult = sc.sub("interpolation_net"), up["channels_multiplier"]
    d, cf = nconv_taps(net_i.sub("nconv_in"), d, cf, mult, up["encoder_filter_sz"])
    d, cf = nconv_taps(net_i.sub("nconv_x2_0"), d, cf, mult, up["encoder_filter_sz"])
    d, cf = nconv_taps(
        net_i.sub("decoder_0"), jnp.concatenate([d, d], -1), jnp.concatenate([cf, cf], -1),
        mult, up["decoder_filter_sz"],
    )
    d, _ = nconv_taps(net_i.sub("nconv_out"), d, cf, 1, up["out_filter_sz"])
    return 8.0 * d.reshape(b, c, h * s, w * s).transpose(0, 2, 3, 1)


def onecycle_lr(step: int, max_lr: float, total_steps: int, pct_start: float = 0.05,
                div_factor: float = 25.0, final_div_factor: float = 1e4) -> float:
    """torch ``OneCycleLR`` (linear anneal, two phases) at optimizer step
    ``step`` (0 for the first update)."""
    initial = max_lr / div_factor
    final = initial / final_div_factor
    warm_end = float(pct_start * total_steps) - 1.0
    last = float(total_steps - 1)
    if step <= warm_end:
        return initial + (max_lr - initial) * (step / warm_end)
    return max_lr + (final - max_lr) * ((step - warm_end) / (last - warm_end))


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(tree)))


class TrainReference:
    """The reference training step for one configuration file's ``model``
    and ``train`` sections."""

    def __init__(self, model: dict, train: dict, precision: str = "highest"):
        self.ref = Reference(model, precision=precision)
        self.t = dict(train)
        self._value_and_grad = jax.jit(jax.value_and_grad(self._loss_fn))
        self._update = jax.jit(self._update_fn)

    # ------------------------------------------------------------- the loss

    def _loss_fn(self, params, batch_stats, image1, image2, flow_gt, valid):
        """Sequence loss of one batch (here: one sample, (1, H, W, .)):
        sum_i gamma^(n-1-i) * mean(valid * |flow_i - gt|), the mean over
        every element, invalid ones counting as zeros; valid means
        ``valid >= 0.5`` and ``|gt| < max_flow`` (``train.py:42-60``)."""
        ref, t = self.ref, self.t
        variables = {"params": params, "batch_stats": batch_stats}
        pyramid, net, inp = ref._encode_fn(variables, image1, image2)
        b, h, w, _ = image1.shape
        coords0 = _coords(b, h // 8, w // 8)
        mag = jnp.sqrt(jnp.sum(flow_gt**2, axis=-1))
        mask = ((valid >= 0.5) & (mag < t["max_flow"])).astype(jnp.float32)[..., None]
        n = int(t["iters"])

        def iteration(carry, weight):
            net, coords1 = carry
            coords1 = lax.stop_gradient(coords1)  # ``coords1.detach()``
            net, up_mask, coords1 = ref._step_fn(variables, pyramid, net, inp, coords1)
            if ref.ncup:
                sc = ref._scope(variables).sub("upsampler")
                flow_up = ncup_upsample_taps(sc, coords1 - coords0, net, ref.up)
            else:
                flow_up = ref._upsample_fn(variables, net, up_mask, coords1)
            return (net, coords1), weight * jnp.mean(mask * jnp.abs(flow_up - flow_gt))

        weights = jnp.asarray([t["gamma"] ** (n - 1 - i) for i in range(n)], jnp.float32)
        _, terms = lax.scan(iteration, (net, coords0), weights)
        return jnp.sum(terms)

    def loss_and_grads(self, variables: dict, batch: dict):
        """Mean loss and mean gradient over the batch's samples, one at a
        time. ``batch``: image1/image2 (B, H, W, 3) in [0, 255], flow
        (B, H, W, 2), valid (B, H, W)."""
        n = batch["image1"].shape[0]
        loss, grads = 0.0, None
        for i in range(n):
            one = {k: jnp.asarray(v[i : i + 1], jnp.float32) for k, v in batch.items()}
            l, g = self._value_and_grad(
                variables["params"], variables.get("batch_stats", {}),
                one["image1"], one["image2"], one["flow"], one["valid"],
            )
            loss = loss + l / n
            g = jax.tree.map(lambda x: x / n, g)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        return loss, grads

    # -------------------------------------------------------- the optimizer

    def clip(self, grads):
        """``clip_grad_norm_(parameters, clip)``; returns (clipped, norm)."""
        norm = global_norm(grads)
        coef = jnp.minimum(self.t["clip"] / (norm + 1e-6), 1.0)
        return jax.tree.map(lambda g: g * coef, grads), norm

    def _update_fn(self, params, grads, m, v, lr, c1, c2):
        """One AdamW update with an already-clipped gradient; ``c1`` and
        ``c2`` are the bias corrections 1 - b1^t, 1 - b2^t."""
        t = self.t
        b1, b2 = 0.9, 0.999
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1.0 - b1) * g, m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1.0 - b2) * g * g, v, grads)

        def new(p, m_, v_):
            p = p * (1.0 - lr * t["wdecay"])
            return p - lr * (m_ / c1) / (jnp.sqrt(v_) / jnp.sqrt(c2) + t["epsilon"])

        return jax.tree.map(new, params, m, v), m, v

    def steps(self, variables: dict, batch: dict, n_steps: int) -> dict:
        """``n_steps`` optimizer steps on the same batch from ``variables``
        with fresh moments, then the loss once more. Returns the losses
        (``n_steps + 1`` of them: before every update and after the last),
        the first step's gradient, clipped gradient and norm, and the
        final parameters."""
        t = self.t
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        zeros = jax.tree.map(jnp.zeros_like, params)
        m, v = zeros, zeros
        total = int(t["num_steps"]) + 100
        out = {"losses": []}
        for k in range(n_steps + 1):
            loss, grads = self.loss_and_grads({"params": params, "batch_stats": stats}, batch)
            out["losses"].append(float(loss))
            if k == n_steps:
                break
            clipped, norm = self.clip(grads)
            if k == 0:
                out.update(grads=grads, clipped=clipped, grad_norm=float(norm))
            lr = onecycle_lr(k, float(t["lr"]), total)
            params, m, v = self._update(
                params, clipped, m, v, jnp.float32(lr),
                jnp.float32(1.0 - 0.9 ** (k + 1)), jnp.float32(1.0 - 0.999 ** (k + 1)),
            )
        out["params"] = params
        return out
