"""Layer-level parity tests (conv transpose, norms, frozen BN)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from raft_ncup_tpu.nn import layers
from raft_ncup_tpu.nn.layers import Conv2d, ConvTranspose2d, Norm


def test_conv_transpose_matches_torch():
    rng = np.random.default_rng(0)
    N, Cin, Cout, H, W, k, s = 2, 3, 5, 4, 6, 2, 2
    x = rng.standard_normal((N, H, W, Cin)).astype(np.float32)
    mod = ConvTranspose2d(Cout, k, stride=s, use_bias=False)
    v = mod.init(jax.random.key(0), jnp.asarray(x))
    ours = np.asarray(mod.apply(v, jnp.asarray(x)))

    # Same weights into torch: ours (kh, kw, out, in) -> torch (in, out, kh, kw).
    w = np.asarray(v["params"]["kernel"]).transpose(3, 2, 0, 1)
    theirs = (
        F.conv_transpose2d(
            torch.from_numpy(x.transpose(0, 3, 1, 2)), torch.from_numpy(w), stride=s
        )
        .permute(0, 2, 3, 1)
        .numpy()
    )
    np.testing.assert_allclose(ours, theirs, atol=1e-5)


def test_instance_norm_matches_torch():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 6, 5, 8)).astype(np.float32)
    mod = Norm("instance")
    v = mod.init(jax.random.key(0), jnp.asarray(x))
    ours = np.asarray(mod.apply(v, jnp.asarray(x)))
    theirs = (
        torch.nn.InstanceNorm2d(8)(torch.from_numpy(x.transpose(0, 3, 1, 2)))
        .permute(0, 2, 3, 1)
        .numpy()
    )
    np.testing.assert_allclose(ours, theirs, atol=1e-5)


def test_group_norm_matches_torch():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 6, 5, 8)).astype(np.float32)
    mod = Norm("group", num_groups=2)
    v = mod.init(jax.random.key(0), jnp.asarray(x))
    ours = np.asarray(mod.apply(v, jnp.asarray(x)))
    theirs = (
        torch.nn.GroupNorm(2, 8)(torch.from_numpy(x.transpose(0, 3, 1, 2)))
        .permute(0, 2, 3, 1)
        .detach()
        .numpy()
    )
    np.testing.assert_allclose(ours, theirs, atol=1e-5)


def test_batch_norm_train_and_frozen():
    """train=True updates stats; train=False (frozen BN) runs off running
    averages without requiring a mutable collection."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 6, 5, 3)).astype(np.float32) * 2 + 1)
    mod = Norm("batch")
    v = mod.init(jax.random.key(0), x)

    # Frozen: stats unused-updated; apply must not demand mutability.
    out_frozen = mod.apply(v, x, train=False)
    np.testing.assert_allclose(
        np.asarray(out_frozen),
        np.asarray(x) / np.sqrt(1 + 1e-5),
        atol=1e-4,
    )

    out_train, mut = mod.apply(v, x, train=True, mutable=["batch_stats"])
    new_mean = np.asarray(
        jax.tree.leaves(mut["batch_stats"])[0]
    )
    assert np.abs(new_mean).max() > 0  # stats moved toward batch mean


def test_batch_norm_train_keeps_torchs_running_variance():
    """Training mode (PR 49): statistics over the whole batch in float32, the
    input normalised by the BIASED variance, the running variance moved toward
    the UNBIASED one (torch; flax's own module keeps the biased one), under
    flax's variable names."""
    rng = np.random.default_rng(1)
    x = (rng.standard_normal((2, 3, 4, 5)) * 3 - 1).astype(np.float32)
    mod = Norm("batch")
    v = mod.init(jax.random.key(0), jnp.asarray(x))
    assert set(v["batch_stats"]["BatchNorm_0"]) == {"mean", "var"}
    y, mut = mod.apply(v, jnp.asarray(x), train=True, mutable=["batch_stats"])
    n = 2 * 3 * 4
    mu, var = x.mean((0, 1, 2)), x.var((0, 1, 2))
    np.testing.assert_allclose(np.asarray(y), (x - mu) / np.sqrt(var + 1e-5), atol=2e-6)
    stats = mut["batch_stats"]["BatchNorm_0"]
    np.testing.assert_allclose(np.asarray(stats["mean"]), 0.1 * mu, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(stats["var"]), 0.9 + 0.1 * var * n / (n - 1), rtol=1e-6)
    assert stats["var"].dtype == jnp.float32
    # a bfloat16 activation: float32 statistics, the activation's dtype out
    y16, mut16 = mod.apply(v, jnp.asarray(x, jnp.bfloat16), train=True, mutable=["batch_stats"])
    assert y16.dtype == jnp.bfloat16 and mut16["batch_stats"]["BatchNorm_0"]["var"].dtype == jnp.float32


def test_frozen_batch_norm_lowers_to_the_module_it_did():
    """The frozen path is flax's own ``BatchNorm`` on its running statistics,
    untouched by PR 49: the lowered module of ``Norm("batch")`` with
    ``train=False`` is, to the letter, that of the branch as it stood."""
    import flax.linen as nn

    class Before(nn.Module):
        @nn.compact
        def __call__(self, x):
            y = nn.BatchNorm(use_running_average=True, momentum=0.9, epsilon=1e-5)(
                x.astype(jnp.float32)
            )
            return y.astype(x.dtype)

    x = jnp.ones((2, 6, 5, 3), jnp.bfloat16)
    v = Norm("batch").init(jax.random.key(0), x)

    def lowered(apply):
        def fn(v, x):
            return apply(v, x)

        return jax.jit(fn).lower(v, x).as_text()

    now = lowered(lambda v, x: Norm("batch").apply(v, x, train=False))
    assert now == lowered(lambda v, x: Before().apply(v, x))
    assert "reduce" not in now  # no statistic is taken


def test_conv2d_torch_default_init_range():
    """torch kaiming_uniform(a=sqrt(5)) => bound sqrt(1/fan_in)."""
    mod = Conv2d(8, 3)
    v = mod.init(jax.random.key(0), jnp.zeros((1, 8, 8, 4)))
    k = np.asarray(v["params"]["kernel"])
    bound = np.sqrt(1.0 / (4 * 9))
    assert k.min() >= -bound and k.max() <= bound
    assert k.std() > bound / 3  # roughly uniform, not degenerate


# ---- thin convolutions folded into one product (PR 29) -----------------

# (kh, kw, Cin, Cout): the update block's two 2-channel sites at the
# benchmark configurations' widths and at the small model's.
FOLDED_SITES = {
    "convf1_7x7_2_to_128": ((7, 7, 2, 128), "folded_in"),
    "convf1_small_7x7_2_to_64": ((7, 7, 2, 64), "folded_in"),
    "conv2_3x3_256_to_2": ((3, 3, 256, 2), "folded_out"),
    "conv2_small_3x3_128_to_2": ((3, 3, 128, 2), "folded_out"),
}


def _assert_thin_conv_is_the_convolution(mod, params, x, g, stride, use_bias):
    """``mod`` (a ``Conv2d`` that ``conv_form`` does not leave 'conv') equals
    ``conv_general_dilated`` at `highest` with torch's ``padding=k//2``:
    output, input, kernel and bias cotangents, to float32 rounding of sums
    taken in another order. Returns the jaxpr of its gradient."""
    kh, kw = params["kernel"].shape[:2]

    def reference(params, x):
        y = jax.lax.conv_general_dilated(
            x, params["kernel"], (stride, stride), ((kh // 2,) * 2, (kw // 2,) * 2),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest")
        return y + params["bias"] if use_bias else y

    def thin(params, x):
        return mod.apply({"params": params}, x)

    out, vjp = jax.vjp(thin, params, x)
    ref, ref_vjp = jax.vjp(reference, params, x)
    assert out.shape == ref.shape == g.shape
    got = jax.tree.leaves((out, vjp(g)))
    want = jax.tree.leaves((ref, ref_vjp(g)))
    assert len(got) == (4 if use_bias else 3)
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32 and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=4e-6 * float(jnp.abs(b).max()))
    return jax.make_jaxpr(jax.grad(
        lambda p, x: (thin(p, x) * g).sum(), argnums=(0, 1)))(params, x)


@pytest.mark.parametrize("use_bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("site", FOLDED_SITES)
def test_folded_conv_is_the_convolution_forward_and_every_cotangent(site, use_bias):
    """A thin ``Conv2d`` site is one ``dot_general`` with its taps folded
    into the thin side, no ``conv_general_dilated`` forward or backward,
    and equals the convolution at `highest` on a non-square plane: output,
    input, kernel and bias cotangents, to float32 rounding of sums taken in
    another order."""
    (kh, kw, cin, cout), _ = FOLDED_SITES[site]
    mod = Conv2d(cout, (kh, kw), use_bias=use_bias)
    keys = jax.random.split(jax.random.PRNGKey(kh * 1000 + cin + cout), 3)
    x = jax.random.normal(keys[0], (2, 9, 13, cin))
    g = jax.random.normal(keys[1], (2, 9, 13, cout))
    params = mod.init(keys[2], x)["params"]
    if use_bias:  # torch's bias bound is tiny at fan_in 2304: make it count
        params = {**params, "bias": jnp.linspace(-1.0, 1.0, cout)}
    jaxpr = str(_assert_thin_conv_is_the_convolution(mod, params, x, g, 1, use_bias))
    assert "conv_general_dilated" not in jaxpr and "dot_general" in jaxpr


# The encoders' stems (PR 48): 7x7 at stride 2 over the 3 image channels,
# torch's ``padding=3``; `raft --small`'s is 32 wide.
STEM_SITES = {
    "stem_7x7_stride_2_3_to_64": (7, 7, 3, 64),
    "stem_small_7x7_stride_2_3_to_32": (7, 7, 3, 32),
}


def _conv_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _conv_eqns(sub)


@pytest.mark.parametrize("use_bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize(
    "hw", [(16, 24), (15, 24), (16, 23), (15, 23), (7, 8)], ids=lambda hw: f"{hw[0]}x{hw[1]}"
)
@pytest.mark.parametrize("site", STEM_SITES)
def test_phased_stem_is_the_strided_convolution_forward_and_every_cotangent(site, hw, use_bias):
    """A thin input at stride (2, 2) is a stride-1 convolution over the
    plane's 2x2 phases with their four column shifts on the channel axis
    (48 channels, 4 row taps): ``floor((H + 6 - 7) / 2) + 1`` rows and as
    many columns by the same rule, whether the padded plane's last row and
    column are read (H, W odd) or not (even), equal to the strided
    convolution at `highest` forward and in every cotangent; no convolution
    of its forward or backward runs at a stride or reads the 3-channel
    plane, and no stepped index became a gather."""
    kh, kw, cin, cout = STEM_SITES[site]
    h, w = hw
    ho, wo = (h + 6 - 7) // 2 + 1, (w + 6 - 7) // 2 + 1
    mod = Conv2d(cout, (kh, kw), stride=2, use_bias=use_bias)
    keys = jax.random.split(jax.random.PRNGKey(h * 100 + w + cout), 3)
    x = jax.random.normal(keys[0], (2, h, w, cin))
    g = jax.random.normal(keys[1], (2, ho, wo, cout))
    params = mod.init(keys[2], x)["params"]
    if use_bias:
        params = {**params, "bias": jnp.linspace(-1.0, 1.0, cout)}
    jaxpr = _assert_thin_conv_is_the_convolution(mod, params, x, g, 2, use_bias)
    convs = list(_conv_eqns(jaxpr.jaxpr))
    assert convs and "gather" not in str(jaxpr)
    for eqn in convs:
        assert eqn.params["window_strides"] == (1, 1)
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
        assert (2, h, w, cin) not in shapes and (kh, kw, cin, cout) not in shapes
        assert any(48 in shape for shape in shapes)  # 4 phases x 4 column shifts x 3


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16], ids=["bfloat16", "float16"])
def test_a_strided_thin_input_of_narrow_operands_stays_the_convolution(dtype):
    """At one MXU pass the stems run in a sixth of the time and the phases'
    copies cost more than they save (`eval_sintel_nc_bf16`: table above
    ``FOLD_MAX_THIN``): the rule reads the operands' itemsize and leaves
    them ``conv_general_dilated``; a stride-1 thin input folds whatever its
    dtype, as before."""
    assert layers.conv_form((7, 7, 3, 64), (2, 2), itemsize=jnp.dtype(dtype).itemsize) == "conv"
    assert layers.conv_form((7, 7, 3, 64), (2, 2)) == "conv"  # a width nobody stated
    assert layers.conv_form((7, 7, 2, 128), (1, 1), itemsize=jnp.dtype(dtype).itemsize) == "folded_in"
    mod = Conv2d(64, 7, stride=2, dtype=dtype)
    x = jnp.ones((1, 8, 10, 3))
    variables = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), x))
    layers.reset_conv_forms()
    jaxpr = str(jax.make_jaxpr(lambda v: mod.apply(v, x))(variables))
    assert "conv_general_dilated" in jaxpr and "optimization_barrier" not in jaxpr
    assert layers.conv_forms()["conv"] == [""] and layers.conv_forms()["phased_in"] == []


# kernel shape, stride, dilation, groups -> the form the rule gives it.
CONV_FORM_TABLE = {
    **{site: (shape, 1, 1, 1, form) for site, (shape, form) in FOLDED_SITES.items()},
    **{site: (shape, 2, 1, 1, "phased_in") for site, shape in STEM_SITES.items()},
    "sep_gru_1x5_thin_in": ((1, 5, 2, 128), 1, 1, 1, "folded_in"),
    "strided_3x3_64_to_96": ((3, 3, 64, 96), 2, 1, 1, "conv"),
    "strided_3x3_onto_a_thin_output": ((3, 3, 64, 2), 2, 1, 1, "conv"),
    "stride_2_by_1_thin_in": ((7, 7, 3, 64), (2, 1), 1, 1, "conv"),
    "stride_3_thin_in": ((7, 7, 3, 64), 3, 1, 1, "conv"),
    "weights_out_1x1_32_to_2": ((1, 1, 32, 2), 1, 1, 1, "conv"),
    "strided_1x1_3_to_64": ((1, 1, 3, 64), 2, 1, 1, "conv"),
    "dilated_3x3_2_to_64": ((3, 3, 2, 64), 1, 2, 1, "conv"),
    "strided_dilated_3x3_2_to_64": ((3, 3, 2, 64), 2, 2, 1, "conv"),
    "grouped_3x3_2_to_64": ((3, 3, 2, 64), 1, 1, 2, "conv"),
    "even_4x4_2_to_64": ((4, 4, 2, 64), 1, 1, 1, "conv"),
    "weights_net_3x3_130_to_64": ((3, 3, 130, 64), 1, 1, 1, "conv"),
    "gru_1x5_384_to_128": ((1, 5, 384, 128), 1, 1, 1, "conv"),
    "just_over_the_width": ((3, 3, layers.FOLD_MAX_THIN + 1, 128), 1, 1, 1, "conv"),
    "just_over_the_width_strided": ((3, 3, layers.FOLD_MAX_THIN + 1, 128), 2, 1, 1, "conv"),
}


@pytest.mark.parametrize("case", CONV_FORM_TABLE)
def test_conv_form_is_read_from_the_kernel_stride_dilation_and_groups(case):
    """The rule's table: only undilated, ungrouped odd kernels with more
    than one tap and a side of at most ``FOLD_MAX_THIN`` channels leave
    ``conv_general_dilated`` as it was: folded at stride 1, either side; a
    thin input at stride (2, 2) (PR 48) a stride-1 convolution over its
    phases. Every other site lowers as before, and the tally says which
    form the site took."""
    shape, stride, dilation, groups, form = CONV_FORM_TABLE[case]
    kh, kw, cin_g, cout = shape
    stride = stride if isinstance(stride, tuple) else (stride,) * 2
    assert layers.conv_form(shape, stride, (dilation,) * 2, groups, itemsize=4) == form
    mod = Conv2d(cout, (kh, kw), stride=stride, dilation=dilation, groups=groups,
                 padding=(dilation * (kh // 2), dilation * (kw // 2)))
    x = jnp.ones((1, 8, 10, cin_g * groups))
    variables = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), x))
    layers.reset_conv_forms()
    jaxpr = jax.make_jaxpr(lambda v: mod.apply(v, x))(variables)
    assert ("conv_general_dilated" in str(jaxpr)) == (form in ("conv", "phased_in"))
    if form == "phased_in":  # not the strided convolution it was
        assert [e.params["window_strides"] for e in _conv_eqns(jaxpr.jaxpr)] == [(1, 1)]
    assert layers.conv_forms() == {
        name: [""] if name == form else [] for name in ("folded_in", "folded_out", "phased_in", "conv")
    }


@pytest.mark.parametrize("config", ["raft_nc_dbl-sintel", "raft-sintel"])
def test_benchmark_models_fold_exactly_the_update_blocks_two_thin_sites(config):
    """Initialising and applying each benchmark configuration's model (a
    toy frame): ``encoder.convf1`` is 'folded_in', ``flow_head.conv2`` is
    'folded_out', the encoders' stem ``conv1`` is 'phased_in', every other
    ``Conv2d`` of the model, the strided ones of the residual stages among
    them, is 'conv'."""
    import json
    import os

    from benchmark.program import build_model

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", f"{config}.json")
    with open(path) as f:
        model = build_model(json.load(f)["model"])
    img = jnp.zeros((1, 64, 96, 3))
    layers.reset_conv_forms()
    variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), (1, 64, 96, 3)))
    jax.eval_shape(lambda v: model.apply(v, img, img, iters=2, test_mode=True), variables)
    forms = layers.conv_forms()
    assert forms["folded_in"] == ["encoder/convf1"]
    assert forms["folded_out"] == ["flow_head/conv2"]
    # ``conv1``: both encoders' stem, one name (PR 48); every other
    # ``conv1`` is a residual block's or the flow head's, below a module.
    assert forms["phased_in"] == ["conv1"]
    # Both encoders (one set of names), the GRU (each gate in two parts since
    # PR 31, and no gate whole), the heads, the weights net: 35 names and
    # more before the stems' left them.
    assert len(forms["conv"]) >= 34 and "gru/convz1" not in forms["conv"]
    assert {"gru/convz1/context", "gru/convz1/step"} <= set(forms["conv"])
    assert {"layer2_0/conv1", "layer3_0/conv1", "layer2_0/downsample_conv"} <= set(forms["conv"])
    assert not set(forms["conv"]) & {"conv1", "encoder/convf1", "flow_head/conv2"}


@pytest.mark.parametrize("kind", ["pac", "djif"])
def test_registry_refuses_a_kind_it_cannot_build(kind):
    """The upsampler registry builds `nconv` and `bilinear`; the kinds of
    the heads that left the tree are a ValueError that names those two,
    not an import of a module that is not there."""
    from raft_ncup_tpu.config import UpsamplerConfig
    from raft_ncup_tpu.nn.upsampler import (
        BilinearUpsampler,
        NConvUpsampler,
        build_upsampler,
    )

    with pytest.raises(ValueError, match="'nconv' and 'bilinear'") as e:
        build_upsampler(UpsamplerConfig(kind=kind), dataset="things")
    assert repr(kind) in str(e.value)
    built = {
        k: type(build_upsampler(UpsamplerConfig(kind=k), dataset="things"))
        for k in ("nconv", "bilinear")
    }
    assert built == {"nconv": NConvUpsampler, "bilinear": BilinearUpsampler}
