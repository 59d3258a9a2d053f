"""RAFT's convex-mask head runs where a prediction is upsampled, and
nowhere else (PERF.md section 6, PR 33).

The mask is a function of the GRU's hidden state alone and feeds nothing
back into the recurrence. In test mode only the last iteration's flow is
upsampled, so the head's two convolutions (``mask_conv1`` 3x3 128 -> 256,
``mask_conv2`` 1x1 256 -> 576) stand ONCE, after the refinement loop,
under the scope ``raft.mask_head``, and no ``(..., 576)`` array rides the
loop's carry or a segment carry. In train mode every iteration's
prediction is an output, so the head is in the loop body.

Structure is read from ``jax.make_jaxpr`` over abstract weights (nothing
compiles); the equivalences run the `raft` variant WITH its mask head at a
toy size, which the small model of ``tests/test_earlyexit.py`` and
``tests/test_pipe_schedule.py`` does not have.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import ModelConfig, flagship_config, small_model_config
from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
from raft_ncup_tpu.models import get_model

HW = (64, 64)
B = 3
ITERS = 4  # divisible by S in {1, 2, 4}
MASK_KERNELS = {"mask_conv1": (3, 3, 128, 256), "mask_conv2": (1, 1, 256, 576)}
LOOPS = ("scan", "while")


# ------------------------------------------------------------- structure


def _abstract(cfg, batch=2):
    model = get_model(cfg)
    shape = (batch, *HW, 3)
    variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), shape))
    return model, variables, jax.ShapeDtypeStruct(shape, jnp.float32)


def _survey(fn, *args):
    """What the jaxpr of ``fn`` holds, inside loops and outside them:
    ``convs[where]`` the number of convolutions, ``mask[where][name]`` the
    kernel shapes of those whose name stack ends in a mask-head layer,
    ``wide[where]`` the number of convolutions onto 576 channels whoever
    names them, and ``carried`` the shapes every loop hands round."""
    out = {
        "convs": {"loop": 0, "after": 0},
        "mask": {"loop": {}, "after": {}},
        "wide": {"loop": 0, "after": 0},
        "scopes": set(),
        "loops": [],
        "carried": [],
    }

    def walk(jaxpr, where):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "conv_general_dilated":
                kernel = tuple(eqn.invars[1].aval.shape)
                stack = str(eqn.source_info.name_stack)
                out["convs"][where] += 1
                out["wide"][where] += kernel[-1] == 576
                for layer in MASK_KERNELS:
                    if f"/{layer}" in stack:
                        out["mask"][where].setdefault(layer, []).append(kernel)
                        out["scopes"].add(stack.split("/BasicUpdateBlock")[0])
            if name in LOOPS:
                out["loops"].append(name)
                out["carried"] += [tuple(v.aval.shape) for v in eqn.outvars]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, "loop" if name in LOOPS else where)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, "after")
    return out


ONCE_AFTER = {name: [kernel] for name, kernel in MASK_KERNELS.items()}


@pytest.mark.parametrize("loop", LOOPS)
def test_test_mode_runs_the_mask_head_once_after_the_loop(loop):
    model, variables, img = _abstract(ModelConfig(variant="raft"))
    kwargs = {"early_exit_tol": 0.01, "return_exec_iters": True} if loop == "while" else {}
    got = _survey(
        lambda v, a, b: model.apply(v, a, b, iters=ITERS, test_mode=True, **kwargs),
        variables, img, img,
    )
    assert got["loops"] == [loop]
    assert got["mask"] == {"loop": {}, "after": ONCE_AFTER}
    assert got["wide"] == {"loop": 0, "after": 1}
    assert got["scopes"] == {"raft.upsample/raft.mask_head"}
    assert not [shape for shape in got["carried"] if shape[-1:] == (576,)]


def test_train_mode_keeps_the_mask_head_in_the_loop():
    """Every iteration's upsampled prediction is an output (the sequence
    loss reads them all), so the head runs in the body, once an
    iteration, and nowhere after it."""
    model, variables, img = _abstract(ModelConfig(variant="raft"))
    got = _survey(
        lambda v, a, b: model.apply(v, a, b, iters=ITERS, train=True, freeze_bn=True),
        variables, img, img,
    )
    assert got["loops"] == ["scan"]
    assert got["mask"] == {"loop": ONCE_AFTER, "after": {}}
    assert got["wide"] == {"loop": 1, "after": 0}
    assert got["scopes"] == {"raft.upsample/raft.mask_head"}


@pytest.mark.parametrize("early_exit", [False, True], ids=["plain", "early_exit"])
def test_segments_carry_no_mask_and_finalize_runs_the_head(early_exit):
    model, variables, img = _abstract(ModelConfig(variant="raft"))
    carry = jax.eval_shape(
        lambda v, a, b: model.encode(v, a, b, early_exit=early_exit), variables, img, img
    )
    keys = {"net", "coords1", "inp", "fmap1", "fmap2"}
    assert set(carry) == keys | ({"converged", "exec_iters"} if early_exit else set())

    def segment(v, c):
        return model.refine_segment(
            v, c, ITERS // 2, early_exit_tol=0.01 if early_exit else None
        )

    assert jax.eval_shape(segment, variables, carry) == carry
    for value in carry.values():
        assert value.shape[-1] != 576

    enc = _survey(lambda v, a, b: model.encode(v, a, b), variables, img, img)
    seg = _survey(segment, variables, carry)
    fin = _survey(lambda v, c: model.finalize(v, c), variables, carry)
    assert enc["mask"] == seg["mask"] == {"loop": {}, "after": {}}
    assert enc["wide"] == seg["wide"] == {"loop": 0, "after": 0}
    assert seg["loops"] == ["scan"]
    assert fin["loops"] == []
    assert fin["mask"] == {"loop": {}, "after": ONCE_AFTER}
    assert fin["scopes"] == {"raft.upsample/raft.mask_head"}
    # finalize is the head, the convex combination and nothing else.
    assert fin["convs"] == {"loop": 0, "after": 2}


# The convolutions of a 64x64 forward as counted on the tree before PR 33
# (loop body, rest of the program). Models without a mask head must not
# move; `raft` moves its two from the body to after the loop in test mode
# and keeps them in the body in train mode.
@pytest.mark.parametrize(
    "cfg, kwargs, convs",
    [
        pytest.param(flagship_config(), {"test_mode": True}, (11, 41), id="nc_test"),
        pytest.param(
            flagship_config(), {"test_mode": True, "early_exit_tol": 0.01}, (11, 41),
            id="nc_early_exit",
        ),
        pytest.param(flagship_config(), {"train": True, "freeze_bn": True}, (14, 38), id="nc_train"),
        pytest.param(
            small_model_config("raft", dataset="chairs"), {"test_mode": True}, (7, 43),
            id="small_test",
        ),
        pytest.param(ModelConfig(variant="raft"), {"test_mode": True}, (13 - 2, 38 + 2), id="raft_test"),
        pytest.param(
            ModelConfig(variant="raft"), {"train": True, "freeze_bn": True}, (13, 38),
            id="raft_train",
        ),
    ],
)
def test_convolution_counts_move_only_where_a_mask_head_exists(cfg, kwargs, convs):
    model, variables, img = _abstract(cfg)
    got = _survey(lambda v, a, b: model.apply(v, a, b, iters=3, **kwargs), variables, img, img)
    assert (got["convs"]["loop"], got["convs"]["after"]) == convs
    if cfg.variant != "raft" or cfg.small:
        assert got["mask"] == {"loop": {}, "after": {}}
        assert got["wide"] == {"loop": 0, "after": 0}


# ----------------------------------------------------------- equivalence


@pytest.fixture(scope="module")
def raft():
    model = get_model(ModelConfig(variant="raft"))
    variables = model.init(jax.random.PRNGKey(0), (1, *HW, 3))
    return model, variables


@pytest.fixture(scope="module")
def images():
    g = np.random.default_rng(11)
    return tuple(
        jnp.asarray(g.random((B, *HW, 3)) * 255.0, jnp.float32) for _ in range(2)
    )


@pytest.fixture(scope="module")
def fwd(raft):
    return ShapeCachedForward(*raft)


@pytest.fixture(scope="module")
def mono(fwd, images):
    lr, up = fwd.forward_device(*images, ITERS)
    return np.asarray(lr), np.asarray(up)


def test_test_mode_flow_is_the_last_train_mode_prediction(raft, images, mono):
    """The head after the loop on the last ``net`` is the head the train
    forward runs in its last iteration: same two convolutions, same
    input. Two programs (a remat'd scan with stacked outputs against a
    bare one), so equal to float32 rounding, not bitwise."""
    model, variables = raft
    seq = jax.jit(
        lambda v, a, b: model.apply(v, a, b, iters=ITERS, train=True, freeze_bn=True)
    )(variables, *images)
    assert seq.shape == (ITERS, B, *HW, 2)
    np.testing.assert_allclose(np.asarray(seq[-1]), mono[1], rtol=0, atol=2e-5)
    # ...and it is the LAST one: the prediction before it is another flow.
    assert np.abs(np.asarray(seq[-2]) - mono[1]).max() > 1e-3


def test_early_exit_lane_is_the_plain_forward_at_its_exec_iters(fwd, images):
    """tests/test_earlyexit.py's freeze contract on the variant with the
    mask: a lane frozen at iteration k kept ``net_k`` bitwise, and the
    head after the loop reads exactly that, as the plain k-iteration
    program's does. Bitwise, across executables."""
    i1, i2 = images
    d1 = np.abs(np.asarray(fwd.forward_device(i1, i2, 1)[0])).mean(axis=(1, 2, 3))
    assert d1.min() < d1.max()
    tol = float(d1.min() + d1.max()) / 2.0
    lr, up, ex = (np.asarray(x) for x in fwd.forward_device(i1, i2, ITERS, early_exit_tol=tol))
    assert 1 <= ex.min() < ex.max() <= ITERS  # lanes froze at different iterations
    for i, k in enumerate(ex):
        ref_lr, ref_up = fwd.forward_device(i1, i2, int(k))
        np.testing.assert_array_equal(lr[i], np.asarray(ref_lr)[i])
        np.testing.assert_array_equal(up[i], np.asarray(ref_up)[i])


@pytest.mark.parametrize("segments", [1, 2, 4])
def test_segments_reproduce_apply_on_the_mask_variant(raft, images, mono, segments):
    model, variables = raft

    @jax.jit
    def composed(v, a, b):
        carry = model.encode(v, a, b)
        for _ in range(segments):
            carry = model.refine_segment(v, carry, ITERS // segments)
        return model.finalize(v, carry)

    lr, up = composed(variables, *images)
    np.testing.assert_allclose(np.asarray(lr), mono[0], rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(up), mono[1], rtol=0, atol=1e-5)
