"""The context features out of the GRU's loop (PR 31).

``nn/update.py``'s GRUs read ``[h, inp, motion]``, and ``inp`` is the same
tensor in every refinement iteration. A gate convolution is linear in its
input channels, so the block computes what it makes of ``inp`` once per
pair (``context``) and the loop convolves ``[h, motion]`` alone (``step``).
Here: the two parts against the full-width formula written out in plain
``jax.numpy`` with the same parameters; the parameter tree as every
checkpoint has it; the traced programs' loops holding no convolution that
reads ``inp``. Tiny shapes, nothing of a whole model is compiled (the
compiled-text form of the loop property hangs on the two programs
``tests/test_tpu_aot_compile.py`` compiles; two segments of
``refine_segment`` against ``apply``, executed, is
``tests/test_pipe_schedule.py::test_seam_composition_equals_full_scan``).
"""

import functools
import math

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import ModelConfig, flagship_config
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.nn import layers
from raft_ncup_tpu.nn.update import (
    BasicUpdateBlock,
    ConvGRU,
    SepConvGRU,
    SmallUpdateBlock,
)
from raft_ncup_tpu.utils import flops

B, H, W = 2, 6, 7
# hidden, context, motion widths; the kernel sizes of a pass, in order.
GRUS = {
    "SepConvGRU": (SepConvGRU, 16, 12, 10, {"1": (1, 5), "2": (5, 1)}),
    "ConvGRU": (ConvGRU, 12, 8, 10, {"": (3, 3)}),
}
QUANTITIES = ("forward", "kernels", "biases", "h", "inp", "motion")


def _conv_ref(x, kernel, bias):
    """'Same' convolution as a sum over the kernel's taps of a shifted plane
    times that tap's (Cin, Cout) matrix: no convolution primitive."""
    kh, kw = kernel.shape[:2]
    xp = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
    h, w = x.shape[1:3]
    return bias + sum(
        jnp.einsum("bhwc,co->bhwo", xp[:, ky : ky + h, kx : kx + w], kernel[ky, kx],
                   precision="highest")
        for ky in range(kh) for kx in range(kw)
    )


def _gru_ref(params, passes, h, inp, motion):
    """The reference's GRU on the full width ``[h, inp, motion]``
    (core/update.py:16-60), one pass per entry of ``passes``."""
    x = jnp.concatenate([inp, motion], axis=-1)
    for suffix in passes:
        def gate(g, hx):
            p = params[f"conv{g}{suffix}"]
            return _conv_ref(hx, p["kernel"], p["bias"])

        hx = jnp.concatenate([h, x], axis=-1)
        z, r = jax.nn.sigmoid(gate("z", hx)), jax.nn.sigmoid(gate("r", hx))
        q = jnp.tanh(gate("q", jnp.concatenate([r * h, x], axis=-1)))
        h = (1 - z) * h + z * q
    return h


@functools.lru_cache(maxsize=None)
def _both_ways(name):
    """Output and gradients (of a fixed random projection of the output) of
    the split GRU and of the full-width formula, same parameters and inputs."""
    cls, hd, cd, md, passes = GRUS[name]
    gru = cls(hd, cd + md, cd)
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    h = jnp.tanh(jax.random.normal(keys[0], (B, H, W, hd)))
    inp = jax.nn.relu(jax.random.normal(keys[1], (B, H, W, cd)))
    motion = jax.random.normal(keys[2], (B, H, W, md))
    params = jax.jit(lambda k: gru.init(k, h, motion, method=_init))(keys[3])["params"]
    proj = jax.random.normal(keys[4], (B, H, W, hd))

    def split(params, h, inp, motion):
        ctx = gru.apply({"params": params}, inp, method="context")
        return gru.apply({"params": params}, h, ctx, motion)

    def full(params, h, inp, motion):
        return _gru_ref(params, passes, h, inp, motion)

    out = {}
    with jax.default_matmul_precision("highest"):
        for tag, fn in (("split", split), ("full", full)):
            h_new, grads = jax.jit(lambda *a, fn=fn: (fn(*a), jax.grad(
                lambda *a: jnp.vdot(fn(*a), proj), argnums=(0, 1, 2, 3)
            )(*a)))(params, h, inp, motion)
            flat = flax.traverse_util.flatten_dict(grads[0], sep="/")
            out[tag] = {
                "forward": {"h_new": h_new},
                "kernels": {k: v for k, v in flat.items() if k.endswith("kernel")},
                "biases": {k: v for k, v in flat.items() if k.endswith("bias")},
                "h": {"h": grads[1]}, "inp": {"inp": grads[2]},
                "motion": {"motion": grads[3]},
            }
    return out, (hd, cd, md, passes)


def _init(gru, h, motion):
    """``init``'s entry: the context of a zero ``inp``, then one update."""
    cd = gru.context_dim
    return gru(h, gru.context(jnp.zeros(h.shape[:3] + (cd,))), motion)


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("name", GRUS)
def test_split_gru_equals_the_full_width_formula(name, quantity):
    """Forward and every gradient, to float32 rounding: the kernels' over
    ALL input rows (the ``inp`` rows' comes through the context term alone),
    every bias, ``h``, ``inp``, ``motion``."""
    out, (hd, cd, md, passes) = _both_ways(name)
    got, want = out["split"][quantity], out["full"][quantity]
    assert sorted(got) == sorted(want)
    if quantity == "kernels":
        assert len(got) == 3 * len(passes)
    for key in want:
        assert got[key].shape == want[key].shape
        scale = float(jnp.abs(want[key]).max())
        assert scale > 1e-3, key  # nothing compared is a zero gradient
        np.testing.assert_allclose(got[key], want[key], rtol=2e-5, atol=2e-6 * scale)
    if quantity == "kernels":
        for key, g in got.items():  # every block of rows carries a gradient
            for rows in (slice(0, hd), slice(hd, hd + cd), slice(hd + cd, None)):
                assert float(jnp.abs(g[:, :, rows]).max()) > 1e-3, (key, rows)


# ------------------------------------------------------- the parameter tree

CP = 4 * 81  # corr_levels x (2 r + 1)^2 at the published radius 4
BASIC_TREE = {
    "encoder/convc1": (1, 1, CP, 256), "encoder/convc2": (3, 3, 256, 192),
    "encoder/convf1": (7, 7, 2, 128), "encoder/convf2": (3, 3, 128, 64),
    "encoder/conv": (3, 3, 256, 126),
    **{f"gru/conv{g}1": (1, 5, 384, 128) for g in "zrq"},
    **{f"gru/conv{g}2": (5, 1, 384, 128) for g in "zrq"},
    "flow_head/conv1": (3, 3, 128, 256), "flow_head/conv2": (3, 3, 256, 2),
}
TREES = {
    "basic_mask": (
        lambda: BasicUpdateBlock(CP, 128, 128, use_mask_head=True), 128, 128,
        {**BASIC_TREE, "mask_conv1": (3, 3, 128, 256), "mask_conv2": (1, 1, 256, 576)},
    ),
    "basic_ncup": (
        lambda: BasicUpdateBlock(CP, 128, 128, use_mask_head=False), 128, 128, BASIC_TREE,
    ),
    "small": (
        lambda: SmallUpdateBlock(CP, 96, 64), 96, 64,
        {
            "encoder/convc1": (1, 1, CP, 96), "encoder/convf1": (7, 7, 2, 64),
            "encoder/convf2": (3, 3, 64, 32), "encoder/conv": (3, 3, 128, 80),
            **{f"gru/conv{g}": (3, 3, 96 + 64 + 82, 96) for g in "zrq"},
            "flow_head/conv1": (3, 3, 96, 128), "flow_head/conv2": (3, 3, 128, 2),
        },
    ),
}


@pytest.mark.parametrize("case", TREES)
def test_update_block_parameter_tree_is_what_checkpoints_hold(case):
    """Name for name and shape for shape the tree of the reference's
    ``state_dict`` (core/update.py) under the importer's OIHW -> HWIO: each
    GRU gate is ONE kernel over ``hidden + context + motion`` input rows
    with one bias, drawn as torch draws it from the fan-in of all rows."""
    make, hd, cd, tree = TREES[case]
    block = make()
    net, inp = jnp.zeros((1, 8, 8, hd)), jnp.zeros((1, 8, 8, cd))
    corr, flow = jnp.zeros((1, 8, 8, CP)), jnp.zeros((1, 8, 8, 2))
    variables = jax.jit(block.init)(jax.random.PRNGKey(0), net, inp, corr, flow)
    assert set(variables) == {"params"}
    flat = flax.traverse_util.flatten_dict(variables["params"], sep="/")
    want = {}
    for site, shape in tree.items():
        want[f"{site}/kernel"], want[f"{site}/bias"] = shape, shape[-1:]
    assert {k: v.shape for k, v in flat.items()} == want
    for site, (kh, kw, cin, _) in tree.items():
        if site.startswith("gru/"):
            bound = math.sqrt(1.0 / (kh * kw * cin))
            for leaf in ("kernel", "bias"):
                top = float(jnp.abs(flat[f"{site}/{leaf}"]).max())
                assert 0.9 * bound < top <= bound, (site, leaf)


# ------------------------------------------------ what the traced loops hold

MODELS = {
    "raft_nc_dbl": lambda: flagship_config(dataset="sintel"),
    "raft": lambda: ModelConfig(variant="raft"),
    "raft_small": lambda: ModelConfig(variant="raft", small=True),
}
PROGRAMS = {
    "test_mode": dict(iters=3, test_mode=True),
    "test_mode_early_exit": dict(iters=3, test_mode=True, early_exit_tol=1e-3),
    "test_mode_warm": dict(iters=3, test_mode=True, flow_init=True, net_init=True),
    "train_forward": dict(iters=2, train=True, freeze_bn=True),
}
LOOPS = ("scan", "while")


def _convolutions(jaxpr, in_loop=False):
    """(inside a scan / while body?, lhs shape, kernel shape) of every
    convolution of a jaxpr, through every nested jaxpr (pjit, checkpoint,
    custom derivatives, the loops themselves)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            found.append((in_loop, eqn.invars[0].aval.shape, eqn.invars[1].aval.shape))
        inner = in_loop or eqn.primitive.name in LOOPS
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _convolutions(sub, inner)
    return found


def _traced(model_name, **kwargs):
    model = RAFT(MODELS[model_name]())
    cfg = model.cfg
    variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), (1, 64, 96, 3)))
    img = jnp.zeros((2, 64, 96, 3))
    if kwargs.pop("flow_init", False):
        kwargs["flow_init"] = jnp.zeros((2, 8, 12, 2))
    if kwargs.pop("net_init", False):
        kwargs["net_init"] = jnp.zeros((2, 8, 12, cfg.hidden_dim))
        kwargs["net_warm"] = jnp.array([True, False])
    layers.reset_conv_forms()
    jaxpr = jax.make_jaxpr(lambda v: model.apply(v, img, img, **kwargs))(variables)
    return cfg, _convolutions(jaxpr.jaxpr), layers.conv_forms()


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("model_name", MODELS)
def test_no_convolution_in_the_loop_reads_the_context_features(model_name, program):
    """In the jaxpr of ``apply``, test mode (scan; ``while_loop`` with
    early exit; warm-started) and the training forward (a checkpointed scan
    body): before the loop exactly the gates' context convolutions, a
    ``context_dim``-wide contraction each; in the loop body the gates over
    ``hidden + motion`` channels and nothing ``hidden + context + motion``
    wide. The tally names both parts of every gate and no whole one."""
    cfg, convs, forms = _traced(model_name, **dict(PROGRAMS[program]))
    hd, cd = cfg.hidden_dim, cfg.context_dim
    md = 82 if cfg.small else 128
    taps = [(3, 3)] * 3 if cfg.small else [(1, 5)] * 3 + [(5, 1)] * 3

    def gate_kernels(in_loop, cin):
        return sorted(
            k[:2] for loop, lhs, k in convs
            if loop == in_loop and k[:2] in set(taps) and k[2:] == (cin, hd)
            and lhs[1:3] == (8, 12)
        )

    assert gate_kernels(False, cd) == sorted(taps)  # the context terms, once
    assert gate_kernels(True, hd + md) == sorted(taps)  # the loop's gates
    assert gate_kernels(True, cd) == [] or hd + md == cd
    full = hd + cd + md
    assert [c for c in convs if full in (c[1][-1], c[2][2])] == []

    gates = ["convz", "convr", "convq"] if cfg.small else [
        f"conv{g}{s}" for s in "12" for g in "zrq"
    ]
    gru_sites = sorted(s for s in forms["conv"] if s.startswith("gru/"))
    assert gru_sites == sorted(f"gru/{g}/{part}" for g in gates for part in ("context", "step"))
    assert not [s for sites in forms.values() for s in sites if s in {f"gru/{g}" for g in gates}]


def test_refine_segment_forms_the_context_terms_at_the_head_of_each_segment():
    """The pipelined path: ``encode``'s carry is the pytree it was (``inp``
    travels, no context term does), and a segment's jaxpr has the six
    context convolutions before its scan and none that reads ``inp`` in it."""
    model = RAFT(flagship_config(dataset="sintel"))
    variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), (1, 64, 96, 3)))
    img = jnp.zeros((2, 64, 96, 3))
    carry = jax.eval_shape(lambda v: model.encode(v, img, img), variables)
    assert sorted(carry) == ["coords1", "fmap1", "fmap2", "inp", "net"]
    assert carry["inp"].shape == (2, 8, 12, 128)
    jaxpr = jax.make_jaxpr(lambda v, c: model.refine_segment(v, c, 2))(variables, carry)
    out = jax.eval_shape(lambda v, c: model.refine_segment(v, c, 2), variables, carry)
    assert jax.tree.structure(out) == jax.tree.structure(carry)
    convs = _convolutions(jaxpr.jaxpr)
    gru = [(loop, k) for loop, _, k in convs if k[:2] in {(1, 5), (5, 1)}]
    assert sorted(gru) == sorted(
        [(False, (*t, 128, 128)) for t in [(1, 5)] * 3 + [(5, 1)] * 3]
        + [(True, (*t, 256, 128)) for t in [(1, 5)] * 3 + [(5, 1)] * 3]
    )


# ------------------------------------------------- the program's own count


def _traced_conv_flops(fn, *args):
    """2 x taps x Cin x Cout x outputs of every convolution and 2 x M x N x
    K of every matrix product in the jaxpr of ``fn``."""

    def count(jaxpr):
        f = 0.0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "conv_general_dilated":
                kh, kw, cin, cout = eqn.invars[1].aval.shape
                f += 2.0 * kh * kw * cin * math.prod(eqn.outvars[0].aval.shape)
            elif eqn.primitive.name == "dot_general":
                (lc, _), _ = eqn.params["dimension_numbers"]
                k = math.prod(eqn.invars[0].aval.shape[i] for i in lc)
                f += 2.0 * k * math.prod(eqn.outvars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                f += count(sub)
        return f

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize(
    "part", ["context_once_per_pair", "step_per_iteration", "mask_once_after_the_loop"]
)
def test_flops_count_follows_the_traced_update_block(part):
    """``utils/flops.py`` counts what the program runs: the six context
    convolutions once per pair (6 x 2 x 5 x 128 x 128 a pixel), an
    iteration whose gates contract ``hidden + motion`` = 256 channels and
    holds no mask head, and the `raft` variant's mask head once a pair."""
    h8, w8 = 5, 6
    block = BasicUpdateBlock(CP, 128, 128, use_mask_head=part == "mask_once_after_the_loop")
    net, inp = jnp.zeros((1, h8, w8, 128)), jnp.zeros((1, h8, w8, 128))
    corr, flow = jnp.zeros((1, h8, w8, CP)), jnp.zeros((1, h8, w8, 2))
    variables = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), net, inp, corr, flow))
    if part == "context_once_per_pair":
        traced = _traced_conv_flops(lambda v: block.apply(v, inp, method="context"), variables)
        assert traced == flops._gru_context_flops(h8, w8, 128, 128)
        assert traced == 6 * 2.0 * 5 * 128 * 128 * h8 * w8
    elif part == "mask_once_after_the_loop":
        traced = _traced_conv_flops(lambda v: block.apply(v, net, method="mask"), variables)
        assert traced == flops._mask_head_flops(h8, w8, 128)
        raft = ModelConfig(variant="raft", dataset="sintel")
        assert (
            flops.forward_flops(raft, 1, 64, 96, 32) - flops.forward_flops(raft, 1, 64, 96, 12)
            == 20 * flops._update_block_flops(8, 12, raft.corr_planes)
        )
    else:
        ctx = jax.eval_shape(lambda v: block.apply(v, inp, method="context"), variables)
        traced = _traced_conv_flops(
            lambda v, c: block.apply(v, net, c, corr, flow, method="step"), variables, ctx
        )
        assert traced == flops._update_block_flops(h8, w8, CP)
    cfg = flagship_config(dataset="sintel")
    encoders = 3 * flops._basic_encoder_flops(64, 96, 256) + 2.0 * (8 * 12) ** 2 * 256
    assert flops.forward_flops(cfg, 1, 64, 96, 0) == encoders + 6 * 2.0 * 5 * 128 * 128 * 8 * 12
