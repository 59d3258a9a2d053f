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

    def reference(params, x):
        y = jax.lax.conv_general_dilated(
            x, params["kernel"], (1, 1), ((kh // 2,) * 2, (kw // 2,) * 2),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest")
        return y + params["bias"] if use_bias else y

    def folded(params, x):
        return mod.apply({"params": params}, x)

    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p, x: (folded(p, x) * g).sum(), argnums=(0, 1)))(params, x))
    assert "conv_general_dilated" not in jaxpr and "dot_general" in jaxpr

    out, vjp = jax.vjp(folded, params, x)
    ref, ref_vjp = jax.vjp(reference, params, x)
    assert out.shape == ref.shape == (2, 9, 13, cout)
    got = jax.tree.leaves((out, vjp(g)))
    want = jax.tree.leaves((ref, ref_vjp(g)))
    assert len(got) == (4 if use_bias else 3)
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32 and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=4e-6 * float(jnp.abs(b).max()))


# kernel shape, stride, dilation, groups -> the form the rule gives it.
CONV_FORM_TABLE = {
    **{site: (shape, 1, 1, 1, form) for site, (shape, form) in FOLDED_SITES.items()},
    "sep_gru_1x5_thin_in": ((1, 5, 2, 128), 1, 1, 1, "folded_in"),
    "stem_7x7_stride_2_3_to_64": ((7, 7, 3, 64), 2, 1, 1, "conv"),
    "weights_out_1x1_32_to_2": ((1, 1, 32, 2), 1, 1, 1, "conv"),
    "dilated_3x3_2_to_64": ((3, 3, 2, 64), 1, 2, 1, "conv"),
    "grouped_3x3_2_to_64": ((3, 3, 2, 64), 1, 1, 2, "conv"),
    "even_4x4_2_to_64": ((4, 4, 2, 64), 1, 1, 1, "conv"),
    "weights_net_3x3_130_to_64": ((3, 3, 130, 64), 1, 1, 1, "conv"),
    "gru_1x5_384_to_128": ((1, 5, 384, 128), 1, 1, 1, "conv"),
    "just_over_the_width": ((3, 3, layers.FOLD_MAX_THIN + 1, 128), 1, 1, 1, "conv"),
}


@pytest.mark.parametrize("case", CONV_FORM_TABLE)
def test_conv_form_is_read_from_the_kernel_stride_dilation_and_groups(case):
    """The rule's table: only stride-1, undilated, ungrouped odd kernels
    with more than one tap and a side of at most ``FOLD_MAX_THIN`` channels
    fold; every other site lowers to ``conv_general_dilated`` as before,
    and the tally says which form the site took."""
    shape, stride, dilation, groups, form = CONV_FORM_TABLE[case]
    kh, kw, cin_g, cout = shape
    assert layers.conv_form(shape, (stride,) * 2, (dilation,) * 2, groups) == form
    mod = Conv2d(cout, (kh, kw), stride=stride, dilation=dilation, groups=groups,
                 padding=(dilation * (kh // 2), dilation * (kw // 2)))
    x = jnp.ones((1, 8, 10, cin_g * groups))
    variables = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), x))
    layers.reset_conv_forms()
    jaxpr = str(jax.make_jaxpr(lambda v: mod.apply(v, x))(variables))
    assert ("conv_general_dilated" in jaxpr) == (form == "conv")
    assert layers.conv_forms() == {
        name: [""] if name == form else [] for name in ("folded_in", "folded_out", "conv")
    }


@pytest.mark.parametrize("config", ["raft_nc_dbl-sintel", "raft-sintel"])
def test_benchmark_models_fold_exactly_the_update_blocks_two_thin_sites(config):
    """Initialising and applying each benchmark configuration's model (a
    toy frame): ``encoder.convf1`` is 'folded_in', ``flow_head.conv2`` is
    'folded_out', every other ``Conv2d`` of the model is 'conv'."""
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
    # Both encoders (one set of names), the GRU (each gate in two parts since
    # PR 31, and no gate whole), the heads, the weights net.
    assert len(forms["conv"]) >= 35 and "gru/convz1" not in forms["conv"]
    assert {"gru/convz1/context", "gru/convz1/step"} <= set(forms["conv"])
    assert not set(forms["conv"]) & {"encoder/convf1", "flow_head/conv2"}


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
