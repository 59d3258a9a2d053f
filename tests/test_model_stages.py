"""The model's three stages against its one-scan forward
(models/raft.py: ``encode`` -> ``refine_segment`` x S -> ``finalize``
against ``apply``).

The stages are a second statement of the test-mode forward (ROADMAP
"Design — debts", D19): they share ``apply``'s step body and upsampling
head, and these tests hold the two statements equal. Only ``finalize``
has a caller outside the tests (the benchmark's mixed-precision
evaluation driver), so nothing else would notice them drift.

- PARITY: S segments, each its own jit program, are tolerance-equal to
  the monolithic scan for both variants and both precisions — the carry
  dict is the COMPLETE state at a segment boundary;
- shape algebra is segmentation-invariant (eval_shape, no compiles);
- a mesh is two sizes: the config refuses the triple that used to name
  a pipeline axis;
- the registry's second kind: ``raft_nc_dbl`` under the bilinear control
  head initialises and runs (here because the ``dbl`` fixture's NCUP twin
  is what it is compared with).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import small_model_config
from raft_ncup_tpu.models import get_model

HW = (32, 32)
ITERS = 4  # divisible by S in {1, 2, 4}


def _build(variant):
    cfg = small_model_config(variant, dataset="chairs")
    model = get_model(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, *HW, 3))
    return model, variables


@pytest.fixture(scope="module")
def raft():
    return _build("raft")


@pytest.fixture(scope="module")
def dbl():
    return _build("raft_nc_dbl")


def _pair(seed, batch=1):
    g = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(g.random((batch, *HW, 3)) * 255.0, jnp.float32)
        for _ in range(2)
    )


def _staged(model, variables, i1, i2, segments, jit=True):
    """encode -> refine_segment x S -> finalize, every stage a program of
    its own when ``jit``: the carry crosses S + 1 jit boundaries."""
    wrap = jax.jit if jit else (lambda f: f)
    encode = wrap(model.encode)
    refine = wrap(
        lambda v, c: model.refine_segment(v, c, ITERS // segments)
    )
    finalize = wrap(model.finalize)
    carry = encode(variables, i1, i2)
    for _ in range(segments):
        carry = refine(variables, carry)
    return finalize(variables, carry)


@pytest.mark.parametrize(
    "variant,segments,precision,tol",
    [
        ("raft", 2, "f32", 1e-5),
        ("raft", 4, "f32", 1e-5),
        ("raft_nc_dbl", 2, "f32", 1e-5),
        ("raft", 2, "bf16_infer", 5e-2),
    ],
)
def test_staged_forward_matches_apply(
    variant, segments, precision, tol, raft, dbl
):
    """The stages, S segments across jit boundaries, against ``apply``'s
    one scan: same (variant, S, precision) and tolerances as the stream
    parities of the scheduler that used to drive them. Under
    ``bf16_infer`` the SAME float32 variables run through a model of
    that policy on both sides."""
    model, variables = raft if variant == "raft" else dbl
    if precision != "f32":
        model = get_model(
            dataclasses.replace(model.cfg, precision=precision)
        )
    i1, i2 = _pair(3, batch=2)
    ref_lr, ref_up = jax.jit(
        lambda v, a, b: model.apply(v, a, b, iters=ITERS, test_mode=True)
    )(variables, i1, i2)
    lr, up = _staged(model, variables, i1, i2, segments)
    assert lr.dtype == ref_lr.dtype and up.dtype == ref_up.dtype
    np.testing.assert_allclose(
        np.asarray(lr), np.asarray(ref_lr), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(
        np.asarray(up), np.asarray(ref_up), rtol=tol, atol=tol
    )


def test_seam_composition_equals_full_scan(raft):
    """Model-level seam pin (no mesh): encode -> refine_segment x2
    -> finalize reproduces apply() exactly — the carry dict is the
    COMPLETE state at a segment boundary."""
    model, variables = raft
    i1, i2 = _pair(11)
    ref_lr, ref_up = model.apply(
        variables, i1, i2, iters=ITERS, test_mode=True
    )
    carry = model.encode(variables, i1, i2)
    carry = model.refine_segment(variables, carry, ITERS // 2)
    carry = model.refine_segment(variables, carry, ITERS // 2)
    lr, up = model.finalize(variables, carry)
    np.testing.assert_allclose(
        np.asarray(lr), np.asarray(ref_lr), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(up), np.asarray(ref_up), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("variant", ["raft", "raft_nc_dbl"])
def test_eval_shape_segmentation_invariant(variant, raft, dbl):
    """Output ShapeDtypeStructs are identical for S in {1, 2, 4} and
    match the monolithic apply — pure shape algebra, no compiles."""
    model, variables = raft if variant == "raft" else dbl
    img = jax.ShapeDtypeStruct((1, *HW, 3), jnp.float32)

    def seg_run(s):
        return jax.eval_shape(
            lambda v, a, b: _staged(model, v, a, b, s, jit=False),
            variables, img, img,
        )

    mono = jax.eval_shape(
        lambda v, a, b: model.apply(v, a, b, iters=ITERS, test_mode=True),
        variables, img, img,
    )
    shapes = {s: seg_run(s) for s in (1, 2, 4)}
    assert shapes[1] == shapes[2] == shapes[4] == mono


def test_serve_config_refuses_a_mesh_triple():
    """``ServeConfig(mesh=(1, 1, 2))`` used to be accepted, quantised the
    budget levels to the segments of a pipeline nothing built, and idled
    the second device. A mesh is (data, spatial); the error says so."""
    from raft_ncup_tpu.config import ServeConfig

    with pytest.raises(ValueError, match=r"two positive sizes, \(data, spatial\)"):
        ServeConfig(mesh=(1, 1, 2))
    with pytest.raises(ValueError, match="two positive sizes"):
        ServeConfig(mesh=(1, 0))
    assert ServeConfig(mesh=(1, 2)).mesh == (1, 2)


def test_raft_nc_dbl_runs_under_the_bilinear_control_head(dbl):
    """``upsampler.kind="bilinear"`` is the control of
    scripts/ncup_vs_bilinear.py and the registry's only other kind: the
    model initialises (a parameter-free head, an empty group), runs one
    test-mode forward, and from the same key shares everything but the
    head with its NCUP twin — the low-resolution flow is the twin's, the
    upsampled one is not."""
    from raft_ncup_tpu.config import UpsamplerConfig

    ncup, ncup_vars = dbl
    cfg = dataclasses.replace(
        ncup.cfg, upsampler=UpsamplerConfig(kind="bilinear")
    )
    model = get_model(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, *HW, 3))
    assert variables["params"]["upsampler"] == {}
    i1, i2 = _pair(5)

    def run(m, v):
        return jax.jit(
            lambda v, a, b: m.apply(v, a, b, iters=2, test_mode=True)
        )(v, i1, i2)

    lr, up = run(model, variables)
    ref_lr, ref_up = run(ncup, ncup_vars)
    assert up.shape == (1, *HW, 2) and bool(jnp.isfinite(up).all())
    np.testing.assert_allclose(
        np.asarray(lr), np.asarray(ref_lr), rtol=1e-6, atol=1e-6
    )
    assert not np.allclose(np.asarray(up), np.asarray(ref_up), atol=1e-3)
