"""Plain reference of one training step of RAFT's first curriculum stage
(github princeton-vl/RAFT ``train_standard.sh``, first command: ``--stage
chairs --batch_size 10 --image_size 368 496``; ``train.py``: no
``freeze_bn()`` for this stage, so the context encoder's BatchNorm layers
TRAIN): ``raft_train.py``'s loss, ``jax.grad``, torch's clip, AdamW and
OneCycle, with one definition of BatchNorm more. Straightforward
``jax.numpy``, float32, every product at ``Precision.HIGHEST``
(``precision="high"`` is the cell's control).

It imports nothing of ``raft_ncup_tpu``; the layers, the convex combination
(``raft.py::convex_upsample``, in every iteration) and the seeded weights are
``reference/raft.py``'s, the optimizer is ``reference/raft_train.py``'s.

BatchNorm in training mode, as ``torch.nn.BatchNorm2d`` computes it
(:func:`batch_norm_train`): the batch's mean and BIASED variance over
(B, H, W) in float32 normalise the input, the gradient flows through both,
and the running mean and variance move by momentum 0.1 toward the batch mean
and the UNBIASED variance (n / (n - 1)); they are returned beside the loss.

What is computed, and where it departs from running the published code on a
batch, each on purpose:

- The statistics couple the samples, so the step cannot go one sample at a
  time as ``raft_train.py``'s does. Only the context encoder has BatchNorm:
  it runs on the WHOLE batch (forward, and ``jax.vjp`` for its backward).
  Everything after its output (the feature encoder, whose instance norm is
  per sample, the volume, the twelve iterations, the loss) is per sample and
  is taken one sample at a time, each sample also giving the cotangent of
  its row of the context encoder's output; the rows' cotangents, stacked,
  go through the context encoder's ``vjp``. By the chain rule that is the
  gradient of the batch's mean loss, computed in blocks that fit beside the
  program on one chip.
- The statistics are over the whole batch of 10. Upstream's ``--gpus 0 1``
  is ``nn.DataParallel``: each GPU normalised its 5 samples and device 0's
  running values were kept. One chip holds the batch whole.
- The iterations are a ``lax.scan``; dropout is 0 and ``--add_noise`` is off
  in the recipe: neither is built. AdamW, the clip and OneCycleLR as
  ``raft_train.py`` states them.

Two controls beside ``precision="high"`` (``control=``): ``bn_frozen``, the
running statistics in the batch's place (what the program would compute if
``freeze_bn`` were wrongly true), and ``stats_not_carried``, batch statistics
used and the running ones returned unchanged (a step that drops them before
the state is updated; the loss and the gradient cannot see it, the running
statistics' row can).
"""

from __future__ import annotations

import copy
import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.raft import (
    Scope, _coords, conv, corr_pyramid, encoder,
)
from benchmark.reference.raft_train import TrainReference, onecycle_lr

CONTROLS = ("bn_frozen", "stats_not_carried")
MOMENTUM = 0.1  # torch's: new = (1 - momentum) * old + momentum * batch's
EPS = 1e-5


# ---------------------------------------------------------------- BatchNorm


def batch_norm_train(sc: Scope, x, new_stats: dict):
    """``torch.nn.BatchNorm2d`` in training mode on NHWC ``x``; the updated
    running pair is written to ``new_stats`` under the layer's path."""
    bn = sc.sub("BatchNorm_0")
    c = x.shape[-1]
    scale, bias = bn.param("scale", (c,), None), bn.param("bias", (c,), None)
    n = x.shape[0] * x.shape[1] * x.shape[2]
    mu = x.mean(axis=(0, 1, 2))
    var = ((x - mu) ** 2).mean(axis=(0, 1, 2))  # biased: what normalises
    new_stats[bn.path] = {
        "mean": (1.0 - MOMENTUM) * bn.stat("mean", (c,), 0.0) + MOMENTUM * mu,
        "var": (1.0 - MOMENTUM) * bn.stat("var", (c,), 1.0) + MOMENTUM * var * (n / (n - 1.0)),
    }
    return (x - mu) / jnp.sqrt(var + EPS) * scale + bias


def _residual_block(sc: Scope, x, planes, stride, bn):
    """``raft.py::residual_block`` with the norm handed in."""
    y = conv(sc.sub("conv1"), x, planes, 3, stride, init="kaiming")
    y = jax.nn.relu(bn(sc.sub("norm1"), y))
    y = conv(sc.sub("conv2"), y, planes, 3, init="kaiming")
    y = jax.nn.relu(bn(sc.sub("norm2"), y))
    if stride != 1:
        x = conv(sc.sub("downsample_conv"), x, planes, 1, stride, init="kaiming")
        x = bn(sc.sub("downsample_norm"), x)
    return jax.nn.relu(x + y)


def encoder_bn(sc: Scope, x, out_dim, bn):
    """``raft.py::encoder`` (BasicEncoder) with the norm handed in."""
    x = conv(sc.sub("conv1"), x, 64, 7, 2, init="kaiming")
    x = jax.nn.relu(bn(sc.sub("norm1"), x))
    for i, (dim, stride) in enumerate(((64, 1), (96, 2), (128, 2)), start=1):
        x = _residual_block(sc.sub(f"layer{i}_0"), x, dim, stride, bn)
        x = _residual_block(sc.sub(f"layer{i}_1"), x, dim, 1, bn)
    return conv(sc.sub("conv2"), x, out_dim, 1, init="kaiming")


def _nested(flat: dict) -> dict:
    """``{("cnet", "norm1", "BatchNorm_0"): leaf}`` as the nested tree."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return out


def bn_layer_count(batch_stats: dict) -> int:
    """BatchNorm layers of a ``batch_stats`` tree: one running mean each."""
    return len(jax.tree.leaves(batch_stats)) // 2


def stats_rel_gap(stats: dict, ref_stats: dict) -> float:
    """Relative L2 gap over every running mean and variance."""
    num = sum(jnp.sum((a - b) ** 2) for a, b in zip(jax.tree.leaves(stats), jax.tree.leaves(ref_stats)))
    den = sum(jnp.sum(b**2) for b in jax.tree.leaves(ref_stats))
    return float(jnp.sqrt(num / den))


# ----------------------------------------------------------------- the step


@functools.lru_cache(maxsize=4)
def _reference_of(model_json: str, train_json: str, precision: str) -> "BNTrainReference":
    return BNTrainReference(json.loads(model_json), json.loads(train_json), precision=precision)


def reference_for(model: dict, train: dict, precision: str = "highest") -> "BNTrainReference":
    """One reference a configuration and precision in this process (a seed
    changes the weights, not the programs): its compiled pieces are found
    again by the next seed's check."""
    return _reference_of(json.dumps(model, sort_keys=True), json.dumps(train, sort_keys=True), precision)


class BNTrainReference:
    """The reference training step with BatchNorm trained, for one
    configuration file's ``model`` (``variant: "raft"``) and ``train``
    sections."""

    def __init__(self, model: dict, train: dict, precision: str = "highest", control: str | None = None):
        if model["variant"] != "raft" or train.get("freeze_bn"):
            raise ValueError("this reference is of the raft model with BatchNorm trained")
        if control is not None and control not in CONTROLS:
            raise ValueError(f"no control {control!r}: {CONTROLS}")
        self.base = TrainReference(model, train, precision=precision)  # clip, AdamW, schedule
        self.ref, self.t, self.control = self.base.ref, dict(train), control
        self._rest = jax.jit(jax.value_and_grad(self._rest_loss_fn, argnums=(0, 1)))
        self._jit_cnet()

    def _jit_cnet(self) -> None:
        self._cnet = jax.jit(self._cnet_fn)
        self._cnet_grad = jax.jit(
            lambda p, s, img, cot: jax.vjp(lambda q: self._cnet_fn(q, s, img)[0], p)[1](cot)[0]
        )

    def with_control(self, control: str) -> "BNTrainReference":
        """This reference with one statement about BatchNorm dropped. The
        per-sample program, which no control touches, is shared, compiled."""
        if control not in CONTROLS:
            raise ValueError(f"no control {control!r}: {CONTROLS}")
        other = copy.copy(self)
        other.control = control
        other._jit_cnet()
        return other

    # ------------------------------------------- the context encoder, batch

    def _cnet_fn(self, cnet_params, cnet_stats, image1):
        """The context encoder on the whole batch: its output and the
        running statistics it leaves."""
        sc = Scope({"cnet": cnet_params}, {"cnet": cnet_stats}, precision=self.ref.precision)
        i1 = 2.0 * (image1 / 255.0) - 1.0
        if self.control == "bn_frozen":
            return encoder(sc.sub("cnet"), i1, 256, "batch"), cnet_stats
        new: dict = {}
        out = encoder_bn(sc.sub("cnet"), i1, 256, lambda s, x: batch_norm_train(s, x, new))
        carried = _nested(new)["cnet"] if self.control != "stats_not_carried" else cnet_stats
        return out, carried

    # -------------------------------------------- the rest, a sample a time

    def _rest_loss_fn(self, params, c, image1, image2, flow_gt, valid):
        """One sample's sequence loss from its row ``c`` of the context
        encoder's output on: ``raft_train.py::_loss_fn`` with the convex
        combination in every iteration."""
        ref, t = self.ref, self.t
        variables = {"params": params, "batch_stats": {}}
        i1, i2 = 2.0 * (image1 / 255.0) - 1.0, 2.0 * (image2 / 255.0) - 1.0
        f = encoder(ref._scope(variables).sub("fnet"), jnp.concatenate([i1, i2], 0), 256, "instance")
        f1, f2 = jnp.split(f, 2, axis=0)
        pyramid = tuple(corr_pyramid(f1, f2, ref.levels, ref.precision))
        net, inp = jnp.tanh(c[..., :128]), jax.nn.relu(c[..., 128:])
        b, h, w, _ = image1.shape
        coords0 = _coords(b, h // 8, w // 8)
        mag = jnp.sqrt(jnp.sum(flow_gt**2, axis=-1))
        mask = ((valid >= 0.5) & (mag < t["max_flow"])).astype(jnp.float32)[..., None]
        n = int(t["iters"])

        def iteration(carry, weight):
            net, coords1 = carry
            coords1 = lax.stop_gradient(coords1)  # ``coords1.detach()``
            net, up_mask, coords1 = ref._step_fn(variables, pyramid, net, inp, coords1)
            flow_up = ref._upsample_fn(variables, net, up_mask, coords1)
            return (net, coords1), weight * jnp.mean(mask * jnp.abs(flow_up - flow_gt))

        weights = jnp.asarray([t["gamma"] ** (n - 1 - i) for i in range(n)], jnp.float32)
        _, terms = lax.scan(iteration, (net, coords0), weights)
        return jnp.sum(terms)

    def loss_and_grads(self, variables: dict, batch: dict):
        """Mean loss, its gradient, and the running statistics the forward
        leaves. ``batch``: image1/image2 (B, H, W, 3) in [0, 255], flow
        (B, H, W, 2), valid (B, H, W)."""
        params, stats = variables["params"], variables["batch_stats"]
        full = {k: jnp.asarray(v, jnp.float32) for k, v in batch.items()}
        n = full["image1"].shape[0]
        c, new_cnet_stats = self._cnet(params["cnet"], stats["cnet"], full["image1"])
        loss, grads, cots = 0.0, None, []
        for i in range(n):
            one = {k: v[i : i + 1] for k, v in full.items()}
            l, (g, g_c) = self._rest(
                params, c[i : i + 1], one["image1"], one["image2"], one["flow"], one["valid"]
            )
            loss = loss + l / n
            g = jax.tree.map(lambda x: x / n, g)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            cots.append(g_c / n)
        g_cnet = self._cnet_grad(params["cnet"], stats["cnet"], full["image1"], jnp.concatenate(cots, 0))
        grads = {**grads, "cnet": jax.tree.map(jnp.add, grads["cnet"], g_cnet)}
        return loss, grads, {**stats, "cnet": new_cnet_stats}

    def steps(self, variables: dict, batch: dict, n_steps: int) -> dict:
        """``raft_train.py::steps`` with the statistics carried: ``n_steps``
        optimizer steps on the same batch from ``variables`` with fresh
        moments, then the loss once more. Besides what that returns:
        ``batch_stats``, the running statistics after ``n_steps`` forwards,
        and ``bn_layers``, the BatchNorm layers that train."""
        base, t = self.base, self.t
        params, stats = variables["params"], variables["batch_stats"]
        zeros = jax.tree.map(jnp.zeros_like, params)
        m, v = zeros, zeros
        total = int(t["num_steps"]) + 100
        out = {"losses": [], "bn_layers": 0 if self.control == "bn_frozen" else bn_layer_count(stats)}
        for k in range(n_steps + 1):
            loss, grads, new_stats = self.loss_and_grads({"params": params, "batch_stats": stats}, batch)
            out["losses"].append(float(loss))
            if k == n_steps:
                break
            stats = new_stats
            clipped, norm_ = base.clip(grads)
            if k == 0:
                out.update(grads=grads, clipped=clipped, grad_norm=float(norm_))
            lr = onecycle_lr(k, float(t["lr"]), total)
            params, m, v = base._update(
                params, clipped, m, v, jnp.float32(lr),
                jnp.float32(1.0 - 0.9 ** (k + 1)), jnp.float32(1.0 - 0.999 ** (k + 1)),
            )
        out.update(params=params, batch_stats=stats)
        return out
