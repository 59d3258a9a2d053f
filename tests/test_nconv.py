"""Normalized-convolution primitive tests against a torch oracle mirroring
core/nconv_modules.py:164-199."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from raft_ncup_tpu.ops import (
    downsample_data_conf,
    nconv2d,
    positivity,
    zero_stuff_upsample,
)


def torch_nconv(data, conf, weight, bias=None, eps=1e-20):
    """Oracle for the reference NConv2d forward (NCHW, OIHW weight)."""
    pad = weight.shape[-1] // 2
    denom = F.conv2d(conf, weight, None, 1, pad)
    nomin = F.conv2d(data * conf, weight, None, 1, pad)
    out = nomin / (denom + eps)
    if bias is not None:
        out = out + bias.view(1, -1, 1, 1)
    s = weight.reshape(weight.shape[0], -1).sum(dim=-1)
    cout = denom / s.view(1, -1, 1, 1)
    return out, cout


def test_nconv2d_matches_torch():
    rng = np.random.default_rng(0)
    B, H, W = 2, 10, 12
    cin, cout, k = 2, 3, 5
    data = rng.standard_normal((B, H, W, cin)).astype(np.float32)
    conf = rng.uniform(0, 1, (B, H, W, cin)).astype(np.float32)
    w = rng.uniform(0.1, 2.0, (k, k, cin, cout)).astype(np.float32)
    b = rng.standard_normal((cout,)).astype(np.float32)

    ours_out, ours_conf = nconv2d(
        jnp.asarray(data), jnp.asarray(conf), jnp.asarray(w), jnp.asarray(b)
    )

    tw = torch.from_numpy(w).permute(3, 2, 0, 1)  # HWIO -> OIHW
    t_out, t_conf = torch_nconv(
        torch.from_numpy(data).permute(0, 3, 1, 2),
        torch.from_numpy(conf).permute(0, 3, 1, 2),
        tw,
        torch.from_numpy(b),
    )
    np.testing.assert_allclose(
        np.asarray(ours_out), t_out.permute(0, 2, 3, 1).numpy(), atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(ours_conf), t_conf.permute(0, 2, 3, 1).numpy(), atol=1e-5
    )


def test_positivity_softplus_matches_torch_beta10():
    x = np.linspace(-3, 3, 13).astype(np.float32)
    ours = np.asarray(positivity(jnp.asarray(x), "softplus"))
    theirs = F.softplus(torch.from_numpy(x), beta=10).numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-6)
    assert (ours >= 0).all()


def test_downsample_conf_based_matches_torch():
    rng = np.random.default_rng(0)
    B, H, W, C = 2, 8, 6, 3
    data = rng.standard_normal((B, H, W, C)).astype(np.float32)
    conf = rng.uniform(0, 1, (B, H, W, C)).astype(np.float32)

    d_ds, c_ds = downsample_data_conf(
        jnp.asarray(data), jnp.asarray(conf), "conf_based"
    )

    tconf = torch.from_numpy(conf).permute(0, 3, 1, 2)
    tdata = torch.from_numpy(data).permute(0, 3, 1, 2)
    c_ref, idx = F.max_pool2d(tconf, 2, 2, return_indices=True)
    c_ref = c_ref / 4
    flat = tdata.flatten(start_dim=2)
    d_ref = flat.gather(dim=2, index=idx.flatten(start_dim=2)).view_as(idx)

    np.testing.assert_allclose(
        np.asarray(c_ds), c_ref.permute(0, 2, 3, 1).numpy(), atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(d_ds), d_ref.permute(0, 2, 3, 1).numpy(), atol=1e-6
    )


def test_downsample_max_pooling():
    rng = np.random.default_rng(0)
    data = rng.standard_normal((1, 4, 4, 2)).astype(np.float32)
    conf = rng.uniform(0, 1, (1, 4, 4, 2)).astype(np.float32)
    d_ds, c_ds = downsample_data_conf(
        jnp.asarray(data), jnp.asarray(conf), "max_pooling"
    )
    t = torch.from_numpy(data).permute(0, 3, 1, 2)
    ref = F.max_pool2d(t, 2, 2).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(np.asarray(d_ds), ref, atol=1e-6)


def test_zero_stuff_positions():
    x = jnp.ones((1, 3, 3, 2))
    out = np.asarray(zero_stuff_upsample(x, 4, 4))
    assert out.shape == (1, 12, 12, 2)
    # Nonzero exactly at rows/cols 2, 6, 10 (sH//2::sH).
    nz = np.nonzero(out[0, :, :, 0])
    assert set(nz[0]) == {2, 6, 10} and set(nz[1]) == {2, 6, 10}
    assert out.sum() == 2 * 9


def test_nconv_gradient_flows():
    """The divide makes gradients fragile; check they're finite."""
    import jax

    def loss_fn(w_raw):
        w = positivity(w_raw)
        data = jnp.ones((1, 6, 6, 1))
        conf = jnp.full((1, 6, 6, 1), 0.5)
        out, _ = nconv2d(data, conf, w)
        return (out**2).sum()

    g = jax.grad(loss_fn)(jnp.full((3, 3, 1, 2), 2.0))
    assert np.isfinite(np.asarray(g)).all()


# Every (k, Cin, Cout) the NCUP stack issues: the shipped configuration
# (channels folded into the batch) and ``channels_to_batch=False``'s.
NCUP_SITES = [(5, 1, 2), (5, 2, 2), (3, 4, 2), (1, 2, 1)]
NCUP_SITES_CHANNELS_KEPT = [(5, 2, 4), (5, 4, 4), (3, 8, 4), (1, 4, 2)]


def _conv_highest(x, w):
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), ((k // 2, k // 2),) * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest")


@pytest.mark.parametrize("k,cin,cout", NCUP_SITES + NCUP_SITES_CHANNELS_KEPT)
def test_tap_sum_is_the_convolution_forward_and_both_cotangents(k, cin, cout):
    """Every NCUP site is under the shape rule, and there ``_conv_same``
    (a float32 tap sum on whole planes, PR 27) equals
    ``conv_general_dilated`` at `highest`, forward and both cotangents of
    ``jax.vjp``, to float32 rounding of sums of signed terms."""
    from raft_ncup_tpu.ops import nconv

    assert nconv.tap_form((k, k, cin, cout))
    keys = jax.random.split(jax.random.PRNGKey(k * 100 + cin * 10 + cout), 3)
    x = jax.random.normal(keys[0], (2, 12, 14, cin))
    w = jax.random.uniform(keys[1], (k, k, cin, cout), minval=0.1)
    g = jax.random.normal(keys[2], (2, 12, 14, cout))
    jaxpr = str(jax.make_jaxpr(jax.value_and_grad(
        lambda x, w: (nconv._conv_same(x, w) * g).sum(), argnums=(0, 1)))(x, w))
    assert "conv_general_dilated" not in jaxpr and "dot_general" not in jaxpr

    out, vjp = jax.vjp(nconv._conv_same, x, w)
    ref, ref_vjp = jax.vjp(_conv_highest, x, w)
    for a, b in zip((out, *vjp(g)), (ref, *ref_vjp(g))):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=2e-6 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("case", ["wide_130_to_64", "16_to_16", "stride_2", "grouped", "even_kernel"])
def test_shape_rule_keeps_everything_else_on_the_mxu(case):
    """The engine is chosen from the kernel's shape, stride and groups
    alone: wide channels (the weights-estimation net's widths), strides,
    groups and even kernels lower to ``conv_general_dilated``, and count
    as 'mxu'."""
    from raft_ncup_tpu.ops import nconv

    shape, stride, groups = {
        "wide_130_to_64": ((3, 3, 130, 64), 1, 1),
        "16_to_16": ((3, 3, 16, 16), 1, 1),
        "stride_2": ((3, 3, 2, 2), 2, 1),
        "grouped": ((3, 3, 1, 2), 1, 2),
        "even_kernel": ((4, 4, 2, 2), 1, 1),
    }[case]
    assert not nconv.tap_form(shape, stride, groups)
    x = jnp.ones((1, 8, 8, shape[2] * groups))
    w = jnp.ones(shape)

    def loss(x, w):
        out, cout_ = nconv2d(x, x, w, stride=stride, groups=groups, impl="xla")
        return out.sum() + cout_.sum()

    nconv.reset_dispatch_counts()
    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w))
    assert "conv_general_dilated" in jaxpr
    counts = nconv.dispatch_counts()
    assert (counts["taps"], counts["mxu"]) == (0, 1)


def _flagship_upsampler(channels_to_batch=True):
    import dataclasses

    from raft_ncup_tpu.config import flagship_config
    from raft_ncup_tpu.nn.upsampler import NConvUpsampler

    cfg = dataclasses.replace(
        flagship_config(dataset="sintel").upsampler, channels_to_batch=channels_to_batch)
    up = NConvUpsampler(cfg, use_bn=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 12, 2))
    guid = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 12, 128))
    return up, up.init(jax.random.PRNGKey(2), x, guid), x, guid


@pytest.mark.parametrize("channels_to_batch", [True, False])
def test_flagship_upsampler_leaves_only_the_weights_net_on_the_mxu(channels_to_batch):
    """In the flagship ``NConvUpsampler`` the only convolutions left are
    the weights-estimation net's three (130 -> 64 -> 32 -> 2 at 1/4
    resolution); in its gradient, those three and their transposes (three
    kernel gradients, three input gradients). None of NCUP's layers is
    one, forward or backward."""
    up, variables, x, guid = _flagship_upsampler(channels_to_batch)

    def loss(v, x, guid):
        return (up.apply(v, x, guid) ** 2).sum()

    forward = str(jax.make_jaxpr(lambda v: up.apply(v, x, guid))(variables))
    backward = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(variables, x, guid))
    assert forward.count("conv_general_dilated") == 3
    assert backward.count("conv_general_dilated") == 9


def test_dispatch_counts_name_the_engine_of_every_ncup_layer():
    """One trace of the flagship ``NConvUNet``: every call site took the
    tap form, none the MXU. Five sites for four layers: the shared encoder
    (``nconv_x2_0``) is traced a second time on the downsampled branch,
    which the reference's decoder wiring never consumes (XLA removes it)."""
    from raft_ncup_tpu.nn.nconv_unet import NConvUNet
    from raft_ncup_tpu.ops import nconv

    net = NConvUNet(in_ch=1)
    d = jnp.ones((2, 16, 24, 1))
    variables = net.init(jax.random.PRNGKey(0), d, d)
    nconv.reset_dispatch_counts()
    jax.make_jaxpr(lambda v: net.apply(v, d, d))(variables)
    assert nconv.dispatch_counts() == {"fused": 0, "fallback": 0, "taps": 5, "mxu": 0}
    assert sorted(variables["params"]) == ["decoder_0", "nconv_in", "nconv_out", "nconv_x2_0"]


@pytest.mark.parametrize("k,cin,cout", NCUP_SITES + NCUP_SITES_CHANNELS_KEPT)
def test_nconv_gradients_are_the_convolutions_own(k, cin, cout):
    """Both cotangents are ``_conv_same``'s own rule (the kernel's tap by
    tap since PR 26, all of it a tap sum since PR 27); data, confidence and
    kernel gradients of the whole normalized convolution equal those of
    the plain two-convolution composition, at every NCUP site's kernel and
    channel counts."""
    keys = jax.random.split(jax.random.PRNGKey(k * 10 + cin), 4)
    data = jax.random.normal(keys[0], (2, 12, 14, cin))
    conf = jax.random.uniform(keys[1], (2, 12, 14, cin), minval=0.1)
    w = jax.random.uniform(keys[2], (k, k, cin, cout), minval=0.1)
    g = jax.random.normal(keys[3], (2, 12, 14, cout))

    def plain(data, conf, w):
        denom = _conv_highest(conf, w)
        return _conv_highest(data * conf, w) / (denom + 1e-20), denom / w.sum(axis=(0, 1, 2))

    def loss(fn):
        def f(data, conf, w):
            out, cout_ = fn(data, conf, w)
            return (out * g).sum() + (cout_ * g).sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(data, conf, w)

    (va, ga), (vb, gb) = loss(lambda d, c, w: nconv2d(d, c, w, impl="xla")), loss(plain)
    if (k, cin, cout) == (1, 2, 1):
        # This site's loss is 0.455, what is left of signed terms whose
        # magnitudes sum to over a hundred; the tap sum adds them in
        # another order than the convolution and reads 3.1e-6 beside it
        # (PR 27). Compared on the scale of its terms; every other site
        # holds PR 26's rel=1e-6.
        scale = float(sum(jnp.abs(o * g).sum() for o in plain(data, conf, w)))
        assert float(va) == pytest.approx(float(vb), abs=1e-6 * scale)
    else:
        assert float(va) == pytest.approx(float(vb), rel=1e-6)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


class TestFusedNConvPallas:
    """Interpret-mode equivalence of the fused Pallas NConv2d
    (raft_ncup_tpu.ops.nconv_pallas) against the XLA composition."""

    def _setup(self, k=5, cin=1, cout=2, shape=(2, 24, 32)):
        g = np.random.default_rng(7)
        B, H, W = shape
        data = jnp.asarray(g.normal(size=(B, H, W, cin)), jnp.float32)
        conf = jnp.asarray(g.random((B, H, W, cin)), jnp.float32)
        weight = positivity(
            jnp.asarray(g.normal(2.0, 0.5, (k, k, cin, cout)), jnp.float32)
        )
        bias = jnp.asarray(g.normal(size=(cout,)), jnp.float32)
        return data, conf, weight, bias

    @pytest.mark.parametrize("k,cin,cout", [(5, 1, 2), (3, 4, 2), (1, 2, 1)])
    def test_matches_xla_composition(self, k, cin, cout):
        from raft_ncup_tpu.ops.nconv_pallas import nconv2d_fused

        data, conf, weight, bias = self._setup(k, cin, cout)
        ref_out, ref_conf = nconv2d(data, conf, weight, bias)
        out, conf_out = nconv2d_fused(data, conf, weight, bias, 1e-20, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref_out), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(conf_out), np.asarray(ref_conf), rtol=1e-5, atol=1e-5
        )

    def test_no_bias(self):
        from raft_ncup_tpu.ops.nconv_pallas import nconv2d_fused

        data, conf, weight, _ = self._setup()
        ref_out, _ = nconv2d(data, conf, weight, None)
        out, _ = nconv2d_fused(data, conf, weight, None, 1e-20, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref_out), rtol=1e-5, atol=1e-5
        )

    def test_gradients_match_xla(self):
        from raft_ncup_tpu.ops.nconv_pallas import nconv2d_fused

        data, conf, weight, bias = self._setup(k=3, shape=(1, 12, 16))

        def loss_fused(d, c, w, b):
            out, co = nconv2d_fused(d, c, w, b, 1e-20, True)
            return (out**2).sum() + (co**2).sum()

        def loss_ref(d, c, w, b):
            out, co = nconv2d(d, c, w, b)
            return (out**2).sum() + (co**2).sum()

        gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(data, conf, weight, bias)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(data, conf, weight, bias)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_dispatch_gating(self):
        from raft_ncup_tpu.ops import nconv_pallas as npk

        assert npk.supported((5, 5, 1, 2), stride=1, groups=1)
        assert not npk.supported((5, 5, 1, 2), stride=2, groups=1)
        assert not npk.supported((4, 4, 1, 2), stride=1, groups=1)
        # Row-tiled: the footprint follows the row WIDTH, not the image
        # height, so 1080p and 4K planes are admitted and only a row too
        # wide for one strip is rejected (the chip's compiler agrees:
        # tests/test_tpu_aot_compile.py).
        assert npk.fits_vmem(368, 768, 1, 2, 5)
        assert npk.fits_vmem(1088, 1920, 1, 2, 5)
        assert npk.fits_vmem(2176, 3840, 4, 2, 3)
        assert not npk.fits_vmem(64, 8192, 2, 2, 5)
        assert not npk.supported((11, 11, 1, 1), stride=1, groups=1)

    def test_pallas_raises_on_tpu_outside_the_surface(self, monkeypatch):
        """On the chip a 'pallas' call that reaches no kernel is an
        error, never XLA under the kernel's name."""
        from raft_ncup_tpu.ops import nconv
        from raft_ncup_tpu.utils import runtime

        monkeypatch.setattr(runtime, "is_tpu_backend", lambda: True)
        x = jnp.ones((1, 8, 8, 1), jnp.float32)
        w = jnp.ones((3, 3, 1, 1), jnp.float32)
        with pytest.raises(RuntimeError, match="use impl='xla'"):
            nconv.nconv2d(x, x, w, stride=2, impl="pallas")

    def test_channel_count_gate(self):
        """VERDICT r3 #3: the kernel body unrolls cout*k*k*cin Python
        iterations; wide-channel shapes must be rejected before they
        become a Mosaic compile-time blowup."""
        from raft_ncup_tpu.ops import nconv_pallas as npk

        assert npk.supported((3, 3, 4, 4), stride=1, groups=1)  # 144
        assert not npk.supported((3, 3, 8, 8), stride=1, groups=1)  # 576
        assert not npk.supported((5, 5, 4, 4), stride=1, groups=1)  # 400

    def test_pallas_fallback_warns_and_counts(self):
        """ADVICE r3 (medium): impl='pallas' falling back to XLA must be
        loud and countable — bench rows labeled nconv=pallas use these
        counters to decide whether the fused kernel actually ran."""
        from raft_ncup_tpu.ops import nconv

        g = np.random.default_rng(11)
        data = jnp.asarray(g.normal(size=(1, 8, 8, 1)), jnp.float32)
        conf = jnp.asarray(g.random((1, 8, 8, 1)), jnp.float32)
        weight = positivity(
            jnp.asarray(g.normal(size=(5, 5, 1, 2)), jnp.float32)
        )
        nconv.reset_dispatch_counts()
        # Off the TPU 'pallas' must run the XLA composition, warn, count
        # a fallback and still produce the XLA result (on the TPU the
        # same call raises instead — test_pallas_raises_on_tpu).
        with pytest.warns(UserWarning, match="cannot run the fused kernel"):
            out, conf_out = nconv.nconv2d(data, conf, weight, impl="pallas")
        counts = nconv.dispatch_counts()
        assert counts == {"fused": 0, "fallback": 1, "taps": 1, "mxu": 0}
        ref_out, ref_conf = nconv.nconv2d(data, conf, weight, impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out))
        np.testing.assert_allclose(
            np.asarray(conf_out), np.asarray(ref_conf)
        )
