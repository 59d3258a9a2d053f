"""Plain reference: RAFT (Teed & Deng, ECCV 2020) and RAFT-NCUP (Eldesokey &
Felsberg, VISAPP 2021) forward passes in straightforward jax.numpy, float32,
every product at ``Precision.HIGHEST`` (the configurations' stated
arithmetic; ``precision="high"``, three bf16 passes on a TPU, is the cells'
control, PERF.md section 2). No kernels, no cache, no scan, no batching
tricks: a Python loop over the refinement iterations around three small
jitted pieces (encode, one iteration, upsample).

It imports nothing of ``raft_ncup_tpu``. It also OWNS the weights: the same
layer definitions run once in "create" mode (``init_variables``) to make the
seeded variables tree, in one jitted call on the device, and the benchmark
hands that tree to the program as a checkpoint would. The tree uses the
checkpoint layout the program loads (``params`` / ``batch_stats``, module
names as in the published code), which is the only thing the two share.

Departures from the published description, each on purpose:

- NConvUNet with ``num_downsampling=1`` (the shipped configuration): the
  published decoder indexes its skip list so that the downsampled branch is
  never consumed and the full-resolution encoder output is concatenated with
  itself (core/nconv_modules.py:128-131). The reference computes exactly what
  is consumed: nconv_in -> nconv_x2_0 -> decoder_0(concat(x, x)) -> nconv_out.
- Weights are random draws of the published initialisers (torch's default
  conv init, kaiming-normal fan-out in the encoders, the softplus-reparam
  init of the normalized convolutions), BatchNorm at its initial statistics.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_DN = ("NHWC", "HWIO", "NHWC")


# ------------------------------------------------------------------ parameters


class Scope:
    """A node of the variables tree. With a key it creates what a layer asks
    for (and returns it); without one it reads."""

    def __init__(self, params: dict, stats: dict, key=None, path: tuple = (),
                 precision=lax.Precision.HIGHEST):
        self.params, self.stats, self.key, self.path = params, stats, key, path
        self.precision = precision  # of every product under this node

    def sub(self, name: str) -> "Scope":
        if self.key is not None:
            p = self.params.setdefault(name, {})
            s = self.stats.setdefault(name, {})
        else:
            # a layer without parameters (instance norm) has no node
            p, s = self.params.get(name, {}), self.stats.get(name, {})
        return Scope(p, s, self.key, self.path + (name,), self.precision)

    def _leaf_key(self, name: str):
        tag = "/".join(self.path + (name,)).encode()
        return jax.random.fold_in(self.key, zlib.crc32(tag) & 0x7FFFFFFF)

    def param(self, name: str, shape: tuple, init) -> jax.Array:
        if self.key is not None:
            self.params[name] = init(self._leaf_key(name), shape)
        return self.params[name]

    def stat(self, name: str, shape: tuple, value: float) -> jax.Array:
        if self.key is not None:
            self.stats[name] = jnp.full(shape, value, jnp.float32)
        return self.stats[name]


def _uniform(bound: float):
    return lambda k, s: jax.random.uniform(k, s, jnp.float32, -bound, bound)


def _normal(std: float):
    return lambda k, s: std * jax.random.normal(k, s, jnp.float32)


def _const(v: float):
    return lambda k, s: jnp.full(s, v, jnp.float32)


def _prune(tree: dict) -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            v = _prune(v)
            if v:
                out[k] = v
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------- layers


def conv(sc: Scope, x, features, ksize, stride=1, init="torch"):
    kh, kw = (ksize, ksize) if isinstance(ksize, int) else ksize
    cin = x.shape[-1]
    fan_in = cin * kh * kw
    if init == "torch":
        kinit = _uniform(math.sqrt(1.0 / fan_in))
    else:  # kaiming normal, fan-out (the encoders)
        kinit = _normal(math.sqrt(2.0 / (features * kh * kw)))
    w = sc.param("kernel", (kh, kw, cin, features), kinit)
    b = sc.param("bias", (features,), _uniform(1.0 / math.sqrt(fan_in)))
    y = lax.conv_general_dilated(
        x, w, (stride, stride), ((kh // 2, kh // 2), (kw // 2, kw // 2)),
        dimension_numbers=_DN, precision=sc.precision,
    )
    return y + b


def norm(sc: Scope, x, kind: str):
    if kind == "instance":  # per sample, per channel, no affine
        mu = x.mean(axis=(1, 2), keepdims=True)
        var = ((x - mu) ** 2).mean(axis=(1, 2), keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5)
    if kind == "batch":  # inference: running statistics
        bn = sc.sub("BatchNorm_0")
        c = x.shape[-1]
        scale = bn.param("scale", (c,), _const(1.0))
        bias = bn.param("bias", (c,), _const(0.0))
        mean = bn.stat("mean", (c,), 0.0)
        var = bn.stat("var", (c,), 1.0)
        return (x - mean) / jnp.sqrt(var + 1e-5) * scale + bias
    raise ValueError(kind)


def residual_block(sc: Scope, x, planes, kind, stride):
    y = conv(sc.sub("conv1"), x, planes, 3, stride, init="kaiming")
    y = jax.nn.relu(norm(sc.sub("norm1"), y, kind))
    y = conv(sc.sub("conv2"), y, planes, 3, init="kaiming")
    y = jax.nn.relu(norm(sc.sub("norm2"), y, kind))
    if stride != 1:
        x = conv(sc.sub("downsample_conv"), x, planes, 1, stride, init="kaiming")
        x = norm(sc.sub("downsample_norm"), x, kind)
    return jax.nn.relu(x + y)


def encoder(sc: Scope, x, out_dim, kind):
    """BasicEncoder: 7x7/2 stem, stages 64/96/128 at strides 1/2/2, 1x1 head."""
    x = conv(sc.sub("conv1"), x, 64, 7, 2, init="kaiming")
    x = jax.nn.relu(norm(sc.sub("norm1"), x, kind))
    for i, (dim, stride) in enumerate(((64, 1), (96, 2), (128, 2)), start=1):
        x = residual_block(sc.sub(f"layer{i}_0"), x, dim, kind, stride)
        x = residual_block(sc.sub(f"layer{i}_1"), x, dim, kind, 1)
    return conv(sc.sub("conv2"), x, out_dim, 1, init="kaiming")


# ----------------------------------------------------------------- correlation


def corr_pyramid(f1, f2, levels, precision):
    """All-pairs correlation <f1(p), f2(q)>/sqrt(C), then 2x2 mean pooling
    over q. Level l: (B, H*W, H/2^l, W/2^l)."""
    b, h, w, c = f1.shape
    vol = jnp.einsum(
        "bpc,bqc->bpq", f1.reshape(b, h * w, c), f2.reshape(b, h * w, c),
        precision=precision,
    ) / math.sqrt(c)
    vol = vol.reshape(b, h * w, h, w)
    out = [vol]
    for _ in range(levels - 1):
        v = out[-1]
        h2, w2 = v.shape[2] // 2, v.shape[3] // 2
        v = v[:, :, : h2 * 2, : w2 * 2].reshape(b, h * w, h2, 2, w2, 2)
        out.append(v.mean(axis=(3, 5)))
    return out


def bilinear_zero(img, x, y):
    """Sample img (N, H, W) at pixel coordinates x, y (N, ...); a corner tap
    outside the image contributes zero."""
    n, h, w = img.shape
    x0, y0 = jnp.floor(x), jnp.floor(y)
    flat = img.reshape(n, h * w)
    out = jnp.zeros(x.shape, jnp.float32)
    for dx in (0, 1):
        for dy in (0, 1):
            xi, yi = x0 + dx, y0 + dy
            wgt = (1.0 - jnp.abs(x - xi)) * (1.0 - jnp.abs(y - yi))
            ok = (xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1)
            idx = (
                jnp.clip(yi, 0, h - 1).astype(jnp.int32) * w
                + jnp.clip(xi, 0, w - 1).astype(jnp.int32)
            )
            val = jnp.take_along_axis(flat, idx.reshape(n, -1), axis=1)
            out = out + jnp.where(ok, wgt, 0.0) * val.reshape(x.shape)
    return out


def corr_lookup(pyramid, coords, radius):
    """(2r+1)^2 window around coords/2^l at every level; the first window
    axis offsets x (core/corr.py:31-37), level-major channel order."""
    b, h, w, _ = coords.shape
    k = 2 * radius + 1
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    off_x = d[:, None] * jnp.ones((1, k))  # first axis varies x
    off_y = d[None, :] * jnp.ones((k, 1))
    out = []
    for lvl, vol in enumerate(pyramid):
        cx = coords[..., 0].reshape(b * h * w, 1, 1) / 2**lvl + off_x
        cy = coords[..., 1].reshape(b * h * w, 1, 1) / 2**lvl + off_y
        v = vol.reshape(b * h * w, vol.shape[2], vol.shape[3])
        out.append(bilinear_zero(v, cx, cy).reshape(b, h, w, k * k))
    return jnp.concatenate(out, axis=-1)


# ---------------------------------------------------------------- update block


def update_block(sc: Scope, net, inp, corr, flow, mask_head: bool):
    enc = sc.sub("encoder")
    cor = jax.nn.relu(conv(enc.sub("convc1"), corr, 256, 1))
    cor = jax.nn.relu(conv(enc.sub("convc2"), cor, 192, 3))
    flo = jax.nn.relu(conv(enc.sub("convf1"), flow, 128, 7))
    flo = jax.nn.relu(conv(enc.sub("convf2"), flo, 64, 3))
    mot = jax.nn.relu(
        conv(enc.sub("conv"), jnp.concatenate([cor, flo], -1), 126, 3)
    )
    x = jnp.concatenate([inp, mot, flow], -1)

    gru = sc.sub("gru")
    h = net
    for tag, ks in (("1", (1, 5)), ("2", (5, 1))):
        hx = jnp.concatenate([h, x], -1)
        z = jax.nn.sigmoid(conv(gru.sub("convz" + tag), hx, 128, ks))
        r = jax.nn.sigmoid(conv(gru.sub("convr" + tag), hx, 128, ks))
        q = jnp.tanh(
            conv(gru.sub("convq" + tag), jnp.concatenate([r * h, x], -1), 128, ks)
        )
        h = (1.0 - z) * h + z * q

    fh = sc.sub("flow_head")
    delta = conv(fh.sub("conv2"), jax.nn.relu(conv(fh.sub("conv1"), h, 256, 3)), 2, 3)
    mask = None
    if mask_head:
        m = jax.nn.relu(conv(sc.sub("mask_conv1"), h, 256, 3))
        mask = 0.25 * conv(sc.sub("mask_conv2"), m, 64 * 9, 1)
    return h, mask, delta


# ------------------------------------------------------------------ upsamplers


def convex_upsample(flow, mask, precision):
    """RAFT's convex combination of the 3x3 neighbourhood, x8."""
    b, h, w, _ = flow.shape
    m = jax.nn.softmax(mask.reshape(b, h, w, 9, 8, 8), axis=3)
    fp = jnp.pad(8.0 * flow, ((0, 0), (1, 1), (1, 1), (0, 0)))
    nb = jnp.stack(
        [fp[:, ky : ky + h, kx : kx + w] for ky in range(3) for kx in range(3)],
        axis=3,
    )  # (b, h, w, 9, 2)
    up = jnp.einsum("bhwkij,bhwkc->bhiwjc", m, nb, precision=precision)
    return up.reshape(b, h * 8, w * 8, 2)


def _zero_stuff(x, s):
    b, h, w, c = x.shape
    out = jnp.zeros((b, h * s, w * s, c), x.dtype)
    return out.at[:, s // 2 :: s, s // 2 :: s].set(x)


def _nearest(x, f):
    return jnp.repeat(jnp.repeat(x, f, axis=1), f, axis=2)


def _softplus10(x):
    return jax.nn.softplus(10.0 * x) / 10.0


def nconv(sc: Scope, data, conf, features, k):
    """Normalized convolution with confidence propagation
    (core/nconv_modules.py:164-199): one non-negative kernel for both."""
    cin = data.shape[-1]
    n = k * k * features
    raw = sc.param(
        "weight_p", (k, k, cin, features),
        lambda key, s: _softplus10(
            2.0 + math.sqrt(2.0 / n) * jax.random.normal(key, s, jnp.float32)
        ),
    )
    w = _softplus10(raw)

    def cv(x):
        return lax.conv_general_dilated(
            x, w, (1, 1), ((k // 2, k // 2), (k // 2, k // 2)),
            dimension_numbers=_DN, precision=sc.precision,
        )

    denom = cv(conf)
    out = cv(data * conf) / (denom + 1e-20)
    return out, denom / w.sum(axis=(0, 1, 2))


def ncup_upsample(sc: Scope, flow_lr, net, up: dict):
    """nearest x2, NCUP x4, values x8 (core/raft_nc_dbl.py:107-112,161)."""
    x_lr = _nearest(flow_lr, 2)
    guid = _nearest(net, 2)  # 'area' interpolation x2 = replication
    b, h, w, c = x_lr.shape
    s = up["scale"]

    west = sc.sub("weights_est_net")
    y = jnp.concatenate([x_lr, guid], -1)
    for i, ch in enumerate(up["weights_est_num_ch"]):
        y = conv(west.sub(f"conv{i}"), y, ch, up["weights_est_filter_sz"][i])
        y = jax.nn.relu(norm(west.sub(f"bn{i}"), y, "batch"))
    conf_lr = jax.nn.sigmoid(
        conv(west.sub("out"), y, c, up["weights_est_filter_sz"][-1])
    )

    # channels to batch: every flow channel is interpolated alone.
    def fold(t):
        t = _zero_stuff(t, s)
        return t.transpose(0, 3, 1, 2).reshape(b * c, h * s, w * s, 1)

    d, cf = fold(x_lr), fold(conf_lr)
    net_i = sc.sub("interpolation_net")
    mult = up["channels_multiplier"]
    d, cf = nconv(net_i.sub("nconv_in"), d, cf, mult, up["encoder_filter_sz"])
    d, cf = nconv(net_i.sub("nconv_x2_0"), d, cf, mult, up["encoder_filter_sz"])
    d, cf = nconv(
        net_i.sub("decoder_0"), jnp.concatenate([d, d], -1),
        jnp.concatenate([cf, cf], -1), mult, up["decoder_filter_sz"],
    )
    d, _ = nconv(net_i.sub("nconv_out"), d, cf, 1, up["out_filter_sz"])
    return 8.0 * d.reshape(b, c, h * s, w * s).transpose(0, 2, 3, 1)


# ----------------------------------------------------------------------- model

_UPSAMPLER_SHIPPED = {
    "kind": "nconv", "scale": 4, "use_data_for_guidance": True,
    "channels_to_batch": True, "use_residuals": False, "est_on_high_res": False,
    "num_downsampling": 1, "use_bias": False, "data_pooling": "conf_based",
    "shared_encoder": True, "use_double_conv": False, "pos_fn": "softplus",
    "weights_est_net": "simple",
}


def _coords(b, h, w):
    y, x = jnp.meshgrid(
        jnp.arange(h, dtype=jnp.float32), jnp.arange(w, dtype=jnp.float32),
        indexing="ij",
    )
    return jnp.broadcast_to(jnp.stack([x, y], -1)[None], (b, h, w, 2))


class Reference:
    """The reference for one configuration file's ``model`` section."""

    def __init__(self, model: dict, precision: str = "highest"):
        self.precision = lax.Precision(precision)
        if model["variant"] not in ("raft", "raft_nc_dbl") or model.get("small"):
            raise ValueError(f"no reference for {model!r}")
        self.ncup = model["variant"] == "raft_nc_dbl"
        self.levels = int(model.get("corr_levels", 4))
        self.radius = int(model.get("corr_radius", 4))
        self.up = dict(model.get("upsampler") or {})
        if self.ncup:
            for k, v in _UPSAMPLER_SHIPPED.items():
                if self.up.get(k, v) != v:
                    raise ValueError(f"no reference for upsampler {k}={self.up[k]!r}")
        self._encode = jax.jit(self._encode_fn)
        self._step = jax.jit(self._step_fn)
        self._upsample = jax.jit(self._upsample_fn)

    # the three pieces -----------------------------------------------------

    def _scope(self, variables, key=None):
        return Scope(variables["params"], variables.get("batch_stats", {}), key,
                     precision=self.precision)

    def _encode_fn(self, variables, image1, image2, key=None):
        sc = self._scope(variables, key)
        i1 = 2.0 * (image1 / 255.0) - 1.0
        i2 = 2.0 * (image2 / 255.0) - 1.0
        f = encoder(sc.sub("fnet"), jnp.concatenate([i1, i2], 0), 256, "instance")
        f1, f2 = jnp.split(f, 2, axis=0)
        c = encoder(sc.sub("cnet"), i1, 256, "batch")
        net, inp = jnp.tanh(c[..., :128]), jax.nn.relu(c[..., 128:])
        return tuple(corr_pyramid(f1, f2, self.levels, self.precision)), net, inp

    def _step_fn(self, variables, pyramid, net, inp, coords1, key=None):
        sc = self._scope(variables, key)
        b, h, w, _ = coords1.shape
        corr = corr_lookup(pyramid, coords1, self.radius)
        flow = coords1 - _coords(b, h, w)
        net, mask, delta = update_block(
            sc.sub("update_block"), net, inp, corr, flow, mask_head=not self.ncup
        )
        return net, mask, coords1 + delta

    def _upsample_fn(self, variables, net, mask, coords1, key=None):
        sc = self._scope(variables, key)
        b, h, w, _ = coords1.shape
        flow_lr = coords1 - _coords(b, h, w)
        if self.ncup:
            return ncup_upsample(sc.sub("upsampler"), flow_lr, net, self.up)
        return convex_upsample(flow_lr, mask, self.precision)

    # public ----------------------------------------------------------------

    def init_variables(self, seed: int) -> dict:
        """The seeded variables tree, made on the device in one jitted call:
        the layer definitions above run once in create mode at a small
        spatial size (no parameter's shape depends on it)."""

        def make(key):
            v = {"params": {}, "batch_stats": {}}
            img = jnp.zeros((1, 64, 64, 3), jnp.float32)
            pyr, net, inp = self._encode_fn(v, img, img, key)
            net, mask, c1 = self._step_fn(v, pyr, net, inp, _coords(1, 8, 8), key)
            self._upsample_fn(v, net, mask, c1, key)
            return {"params": _prune(v["params"]), "batch_stats": _prune(v["batch_stats"])}

        word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
        return jax.jit(make)(jax.random.key(word & 0x7FFFFFFF))

    def flow(self, variables, image1, image2, iters: int) -> jax.Array:
        """Full-resolution flow for NHWC float32 images in [0, 255] whose
        height and width divide by 8."""
        image1 = jnp.asarray(image1, jnp.float32)
        image2 = jnp.asarray(image2, jnp.float32)
        pyr, net, inp = self._encode(variables, image1, image2)
        b, h, w, _ = image1.shape
        coords1 = _coords(b, h // 8, w // 8)
        mask = None
        for _ in range(iters):
            net, mask, coords1 = self._step(variables, pyr, net, inp, coords1)
        return self._upsample(variables, net, mask, coords1)


def pad_sintel(image: np.ndarray) -> tuple[np.ndarray, tuple[int, int]]:
    """Replicate-pad an (H, W, 3) frame to multiples of 8, the vertical pad
    centred (upstream RAFT's InputPadder, mode 'sintel'). Returns the padded
    frame and the (top, left) offset of the original in it."""
    h, w = image.shape[:2]
    ph, pw = (-h) % 8, (-w) % 8
    top, left = ph // 2, pw // 2
    out = np.pad(
        image, ((top, ph - top), (left, pw - left), (0, 0)), mode="edge"
    )
    return out, (top, left)


def reference_flow(ref: Reference, variables, image1, image2, iters: int) -> np.ndarray:
    """Native-shape (H, W, 2) float32 flow of one unpadded frame pair."""
    h, w = image1.shape[:2]
    p1, (top, left) = pad_sintel(np.asarray(image1, np.float32))
    p2, _ = pad_sintel(np.asarray(image2, np.float32))
    up = ref.flow(variables, p1[None], p2[None], iters)
    return np.asarray(jax.device_get(up))[0, top : top + h, left : left + w]
