"""Correlation volume + lookup vs the reference math (torch oracle) and
cross-implementation equivalence (volume vs on-the-fly)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from raft_ncup_tpu.ops import (
    build_corr_pyramid,
    coords_grid,
    corr_lookup,
    corr_lookup_onthefly,
)
from raft_ncup_tpu.ops.corr import (
    CorrPyramid,
    DifferentiatedCorrPyramid,
    build_loop_pyramid,
)
from raft_ncup_tpu.ops.geometry import grid_sample


def torch_corr_block(fmap1, fmap2, num_levels=4, radius=4):
    """Reimplementation of the reference CorrBlock (core/corr.py:6-55) as a
    test oracle (NCHW torch tensors in, (B, L*K*K, H, W) out)."""
    batch, dim, ht, wd = fmap1.shape
    f1 = fmap1.view(batch, dim, ht * wd)
    f2 = fmap2.view(batch, dim, ht * wd)
    corr = torch.matmul(f1.transpose(1, 2), f2)
    corr = corr.view(batch * ht * wd, 1, ht, wd) / torch.sqrt(
        torch.tensor(dim).float()
    )
    pyramid = [corr]
    for _ in range(num_levels - 1):
        corr = F.avg_pool2d(corr, 2, stride=2)
        pyramid.append(corr)

    def lookup(coords):
        r = radius
        coords = coords.permute(0, 2, 3, 1)
        batch, h1, w1, _ = coords.shape
        out_pyramid = []
        for i, corr in enumerate(pyramid):
            dx = torch.linspace(-r, r, 2 * r + 1)
            dy = torch.linspace(-r, r, 2 * r + 1)
            delta = torch.stack(torch.meshgrid(dy, dx, indexing="ij"), axis=-1)
            centroid_lvl = coords.reshape(batch * h1 * w1, 1, 1, 2) / 2**i
            delta_lvl = delta.view(1, 2 * r + 1, 2 * r + 1, 2)
            coords_lvl = centroid_lvl + delta_lvl
            H, W = corr.shape[-2:]
            xgrid, ygrid = coords_lvl.split([1, 1], dim=-1)
            xgrid = 2 * xgrid / (W - 1) - 1
            ygrid = 2 * ygrid / (H - 1) - 1
            grid = torch.cat([xgrid, ygrid], dim=-1)
            sampled = F.grid_sample(corr, grid, align_corners=True)
            out_pyramid.append(sampled.view(batch, h1, w1, -1))
        out = torch.cat(out_pyramid, dim=-1)
        return out.permute(0, 3, 1, 2).contiguous().float()

    return lookup


@pytest.mark.parametrize("radius", [3, 4])
def test_corr_volume_lookup_matches_torch(radius):
    # H, W large enough that the deepest pyramid level is > 1 pixel (the
    # reference's coordinate normalization divides by W-1).
    rng = np.random.default_rng(0)
    B, H, W, C = 2, 16, 24, 16
    f1 = rng.standard_normal((B, H, W, C)).astype(np.float32)
    f2 = rng.standard_normal((B, H, W, C)).astype(np.float32)
    coords = (
        coords_grid(B, H, W)
        + rng.uniform(-3, 3, size=(B, H, W, 2)).astype(np.float32)
    )

    pyr = build_corr_pyramid(jnp.asarray(f1), jnp.asarray(f2), num_levels=4)
    ours = np.asarray(corr_lookup(pyr, jnp.asarray(coords), radius))

    t1 = torch.from_numpy(f1).permute(0, 3, 1, 2)
    t2 = torch.from_numpy(f2).permute(0, 3, 1, 2)
    tcoords = torch.from_numpy(np.asarray(coords)).permute(0, 3, 1, 2)
    lookup = torch_corr_block(t1, t2, num_levels=4, radius=radius)
    theirs = lookup(tcoords).permute(0, 2, 3, 1).numpy()

    np.testing.assert_allclose(ours, theirs, atol=1e-4)


def test_onthefly_matches_volume():
    rng = np.random.default_rng(1)
    B, H, W, C = 1, 16, 22, 8
    f1 = jnp.asarray(rng.standard_normal((B, H, W, C)).astype(np.float32))
    f2 = jnp.asarray(rng.standard_normal((B, H, W, C)).astype(np.float32))
    coords = coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-4, 4, size=(B, H, W, 2)).astype(np.float32)
    )
    pyr = build_corr_pyramid(f1, f2, num_levels=4)
    vol = np.asarray(corr_lookup(pyr, coords, radius=4))
    otf = np.asarray(
        corr_lookup_onthefly(f1, f2, coords, radius=4, num_levels=4, row_chunk=3)
    )
    np.testing.assert_allclose(vol, otf, atol=2e-4)


LOOP_PYRAMIDS = {  # what a refinement loop builds -> the widths of 24 / 12 / 6 / 3
    "forward_only": (lambda f: build_loop_pyramid(f, f, 4), CorrPyramid, (24, 16, 8, 8)),
    "differentiated": (
        lambda f: build_loop_pyramid(f, f, 4, differentiated=True),
        DifferentiatedCorrPyramid, (24, 12, 6, 3),
    ),
    "plain": (lambda f: build_corr_pyramid(f, f, num_levels=4), CorrPyramid, (24, 12, 6, 3)),
}


@pytest.mark.parametrize("which", list(LOOP_PYRAMIDS))
def test_corr_pyramid_shapes(which):
    """``build_corr_pyramid`` gives every level at its own width, whoever
    asks; a refinement loop that is not differentiated stores a level whose
    width is no multiple of 8 at the next one (``ops/corr.py::stored_width``),
    one that is gets the plain builder's pyramid under its own type."""
    build, kind, widths = LOOP_PYRAMIDS[which]
    B, H, W, C = 2, 16, 24, 4
    pyr = build(jnp.zeros((B, H, W, C)))
    assert type(pyr) is kind and pyr.query_hw == (H, W)
    assert [lvl.shape for lvl in pyr.levels] == [
        (B, H * W, H >> lvl, width) for lvl, width in enumerate(widths)
    ]
    out = corr_lookup(pyr, coords_grid(B, H, W), radius=4)
    assert out.shape == (B, H, W, 4 * 81)


# Widths that misfit as `eval_kitti_nc`'s do: 20 -> 10 -> 5 -> 2 is even at
# level 0 (156 -> 78 -> 39 -> 19), 23 -> 11 -> 5 -> 2 drops a column at every
# pooling (153 -> 76), 13 -> 6 -> 3 -> 1 ends one column wide.
MISFIT_WIDTHS = [(10, 20), (9, 23), (9, 13)]


def _hw_id(hw) -> str:
    return "x".join(map(str, hw))


@pytest.mark.parametrize("stored", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("hw", MISFIT_WIDTHS, ids=_hw_id)
def test_stored_levels_are_the_levels_own_columns_and_zeros(hw, stored):
    """Every level of the pyramid of a loop that is not differentiated holds
    the columns of the level at its own width (what ``build_corr_pyramid``
    builds, for a loop that is differentiated too: an odd width pools VALID, no
    half-weight column; to the rounding of a product blocked for another
    width, on this backend) and exact zeros after them up to a multiple of
    8."""
    H, W = hw
    rng = np.random.default_rng(12)
    f1, f2 = (
        jnp.asarray(rng.standard_normal((2, H, W, 8)).astype(np.float32))
        for _ in range(2)
    )
    ours = jax.jit(build_loop_pyramid, static_argnums=(2, 3))(f1, f2, 4, stored)
    own = jax.jit(build_corr_pyramid, static_argnums=(2, 3))(f1, f2, 4, stored)
    assert type(ours) is type(own) is CorrPyramid and ours.query_hw == own.query_hw == hw
    for lvl, (got, want) in enumerate(zip(ours.levels, own.levels)):
        wl = W >> lvl
        assert want.shape == (2, H * W, H >> lvl, wl) and want.dtype == stored
        assert got.shape == (2, H * W, H >> lvl, -(-wl // 8) * 8) and got.dtype == stored
        got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
        assert want.any() and got.shape != want.shape
        np.testing.assert_allclose(got[..., :wl], want, atol=2e-6, rtol=0)
        assert not got[..., wl:].any()


@pytest.mark.parametrize(
    "width,differentiated,tally",
    [
        (128, False, ["dot", "multiply_reduce", "multiply_reduce", "multiply_reduce"]),
        (96, True, ["multiply_reduce"] * 4),
        (96, False, ["multiply_reduce"] * 3 + ["multiply_reduce@16"]),
        (156, True, ["multiply_reduce"] * 4),
        (156, False, ["multiply_reduce@160", "multiply_reduce@80",
                      "multiply_reduce@40", "multiply_reduce@24"]),
        (132, False, ["multiply_reduce@136", "multiply_reduce@72",
                      "multiply_reduce@40", "multiply_reduce"]),
    ],
    ids=["128", "96-differentiated", "96", "156-differentiated", "156", "132"],
)
def test_only_a_misfit_width_of_a_forward_only_pyramid_traces_a_pad(
    width, differentiated, tally
):
    """The Sintel grid (128 / 64 / 32 / 16 columns) and any pyramid built
    for a program that differentiates its lookup (a training crop's 96 / 48
    / 24 / 12, where a forward-only program stores level 3 at 16) trace no
    ``pad``, the tally they always had and the plain builder's jaxpr, letter
    for letter; a width of `eval_kitti_nc`'s traces one ``pad`` a level that
    misfits (156 -> 78 -> 39 -> 19: all four; 132 -> 66 -> 33 -> 16: three)
    and names the stored width in its tally."""
    from raft_ncup_tpu.ops import corr

    H, C = 8, 4
    f = jax.ShapeDtypeStruct((1, H, width, C), jnp.float32)
    coords = jax.ShapeDtypeStruct((1, H, width, 2), jnp.float32)

    def program(f1, f2, c):
        pyr = build_loop_pyramid(f1, f2, 4, differentiated=differentiated)
        return corr_lookup(pyr, c, 4)

    def plain(f1, f2, c):  # every level at its own width
        pyr = build_corr_pyramid(f1, f2, 4, differentiated=differentiated)
        return corr_lookup(pyr, c, 4)

    theirs = jax.make_jaxpr(plain)(f, f, coords)
    corr.reset_contract_forms()
    traced = jax.make_jaxpr(program)(f, f, coords)
    assert corr.contract_forms() == {
        f"level{lvl}": f"{form}/float32" for lvl, form in enumerate(tally)
    }
    stored_wider = sum("@" in form for form in tally)
    names = [e.primitive.name for e in _walk(traced.jaxpr)]
    assert names.count("pad") == stored_wider
    assert (str(traced) == str(theirs)) == (stored_wider == 0)


# ---------------------------------------------------------------------------
# The gather-free lookup against the formulation it replaced. The oracle
# below IS the old ``corr_lookup``: the (2r+1)^2 taps of every query
# sampled one by one through ``grid_sample``'s four scalar gathers.


def gather_lookup_oracle(pyramid, coords, radius):
    B, H, W, _ = coords.shape
    K = 2 * radius + 1
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    di, dj = jnp.meshgrid(d, d, indexing="ij")
    delta = jnp.stack([di, dj], axis=-1)  # first window axis offsets x
    out = []
    for lvl, corr in enumerate(pyramid.levels):
        _, _, Hl, Wl = corr.shape
        centroid = coords.reshape(B, H * W, 1, 1, 2) / (2**lvl)
        taps = (centroid + delta[None, None]).reshape(B * H * W, K, K, 2)
        sampled = grid_sample(corr.reshape(B * H * W, Hl, Wl, 1), taps)
        out.append(sampled.reshape(B, H, W, K * K))
    return jnp.concatenate(out, axis=-1)


# The oracle tests call both sides as one program each: op by op on the
# CPU the lookup alone is ~3 s a case, compiled 0.3 s.
_lookup = jax.jit(corr_lookup, static_argnums=2)
_oracle = jax.jit(gather_lookup_oracle, static_argnums=2)


def _random_pyramid(
    seed, B, H, W, dtype=jnp.float32, differentiated=False, build=build_loop_pyramid
):
    """What a refinement loop builds (``build_loop_pyramid``), or the same
    features through ``build=build_corr_pyramid``."""
    rng = np.random.default_rng(seed)
    f1 = jnp.asarray(rng.standard_normal((B, H, W, 8)).astype(np.float32))
    f2 = jnp.asarray(rng.standard_normal((B, H, W, 8)).astype(np.float32))
    return build(f1, f2, num_levels=4, dtype=dtype, differentiated=differentiated)


def _jittered_coords(seed, B, H, W, spread):
    rng = np.random.default_rng(seed)
    return coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-spread, spread, size=(B, H, W, 2)).astype(np.float32)
    )


def _assert_matches_oracle(ours, pyr, coords, radius):
    """Against the gathers over each level's OWN columns: what a level is
    stored with beyond them (``ops/corr.py::stored_width``) the oracle
    never sees, so a tap there must read as one outside the level."""
    W = pyr.query_hw[1]
    own = CorrPyramid(
        tuple(lvl[..., : W >> i] for i, lvl in enumerate(pyr.levels)), pyr.query_hw
    )
    np.testing.assert_allclose(
        np.asarray(ours),
        np.asarray(_oracle(own, coords, radius)),
        atol=2e-6, rtol=1e-6,
    )


# 11x16 is the eval cell's 55x128 scaled down: pooling drops an odd row
# at levels 0 -> 1 (11 -> 5) and 1 -> 2 (5 -> 2), as 55 -> 27 -> 13 does.
# A 128-wide level 0 (whole lanes) takes the lookup's matmul form for x;
# every narrower level takes multiply + reduce.
# The last three are widths no level of which is a multiple of 8 as it
# stands (``MISFIT_WIDTHS``): every level is stored wider than its own.
SHAPES = pytest.mark.parametrize(
    "hw", [(11, 16), (16, 24), (9, 128), *MISFIT_WIDTHS], ids=_hw_id
)
_BOTH_FORMS = [(11, 16), (9, 128)]
BOTH_FORMS = pytest.mark.parametrize("hw", _BOTH_FORMS, ids=_hw_id)
STORED_NARROW = pytest.mark.parametrize("hw", [*_BOTH_FORMS, (17, 23)], ids=_hw_id)


@pytest.mark.parametrize("radius", [3, 4])
@SHAPES
def test_lookup_matches_gather_oracle(hw, radius):
    B, (H, W) = 2, hw
    pyr = _random_pyramid(2, B, H, W)
    coords = _jittered_coords(3, B, H, W, 6)
    ours = _lookup(pyr, coords, radius)
    assert ours.dtype == jnp.float32
    assert ours.shape == (B, H, W, 4 * (2 * radius + 1) ** 2)
    _assert_matches_oracle(ours, pyr, coords, radius)


def _special_coords(case, H, W):
    """(1, H, W, 2) query centres for one edge case of the window."""
    base = coords_grid(1, H, W)
    if case == "integers":  # dx = dy = 0 at level 0, halves further down
        return base + jnp.asarray([2.0, -1.0])
    frac = jnp.asarray([0.25, 0.625])
    x, y = {
        "left": (-0.5, H / 2), "right": (W - 0.5, H / 2),
        "top": (W / 2, -0.5), "bottom": (W / 2, H - 0.5),
        "corner": (W - 0.5, H - 0.5),
        "outside": (-80.0, -80.0),  # no tap of any level lands inside
        "far_outside": (1e6, -1e6),
    }[case]
    return jnp.zeros_like(base) + jnp.asarray([x, y]) + (
        0.0 if case == "far_outside" else frac * base / max(H, W)
    )


BUILDERS = {"loop": build_loop_pyramid, "plain": build_corr_pyramid}


@pytest.mark.parametrize("builder", list(BUILDERS))
@pytest.mark.parametrize("hw", [(11, 16), (11, 13)], ids=["11x16", "11x13"])
@pytest.mark.parametrize(
    "case",
    ["integers", "left", "right", "top", "bottom", "corner", "outside",
     "far_outside"],
)
def test_lookup_window_edges_match_gather_oracle(case, hw, builder):
    """At 11x13 the loop's builder stores every level wider than its own
    (13 -> 16, 6 -> 8, 3 -> 8, 1 -> 8; at 11x16 levels 2-3): the windows of
    ``right`` and ``corner`` hang over the level's last column into the zeros
    it is stored with, where the plain builder's hang over the level's end."""
    (H, W), radius = hw, 4
    pyr = _random_pyramid(4, 1, H, W, build=BUILDERS[builder])
    assert (pyr.levels[3].shape[3] == W >> 3) == (builder == "plain")
    coords = _special_coords(case, H, W)
    ours = np.asarray(_lookup(pyr, coords, radius))
    _assert_matches_oracle(ours, pyr, coords, radius)
    if case in ("outside", "far_outside"):
        assert not ours.any()  # padding_mode='zeros': an all-zero window
    else:
        assert ours.any()


@pytest.mark.parametrize("stored", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("hw", MISFIT_WIDTHS, ids=_hw_id)
def test_lookup_over_the_loop_pyramid_is_the_lookup_over_the_plain_one(hw, stored):
    """The zero columns are weights on exact zeros: the lookup over a pyramid
    stored wider answers what it answers over ``build_corr_pyramid``'s (to the
    rounding of a product blocked for another width, on this backend), with
    queries jittered far enough that windows reach past both ends of a row."""
    B, (H, W), radius = 2, hw, 4
    ours, plain = (
        _random_pyramid(11, B, H, W, dtype=stored, build=build)
        for build in BUILDERS.values()
    )
    assert [lvl.shape[3] for lvl in plain.levels] == [W >> lvl for lvl in range(4)]
    assert all(a.shape[3] > b.shape[3] for a, b in zip(ours.levels, plain.levels))
    coords = _jittered_coords(12, B, H, W, 6)
    got, want = (np.asarray(_lookup(pyr, coords, radius)) for pyr in (ours, plain))
    assert want.any()
    # float32: read 0 to 7.2e-7 here. bfloat16: read 0; the room is one ulp of
    # a stored value under 8, should a product fall across a rounding tie
    np.testing.assert_allclose(
        got, want, atol=4e-6 if stored == jnp.float32 else 2.0**-6, rtol=0
    )


@STORED_NARROW
def test_lookup_widens_a_bf16_volume_to_float32(hw):
    """``PrecisionPolicy.corr_jnp`` stores the volume in bf16; the
    interpolation still runs, and answers, in float32. At 17x23 level 0
    (17 rows of 23 columns, stored 24 wide: 408 elements) takes the tap
    sums over its stored width."""
    B, (H, W), radius = 1, hw, 4
    pyr = _random_pyramid(5, B, H, W, dtype=jnp.bfloat16)
    assert all(lvl.dtype == jnp.bfloat16 for lvl in pyr.levels)
    coords = _jittered_coords(6, B, H, W, 3)
    ours = _lookup(pyr, coords, radius)
    assert ours.dtype == jnp.float32
    # Against the oracle on the SAME bf16 values: were the weights or the
    # products rounded to bf16 the gap would be ~1e-2, not ~1e-6.
    _assert_matches_oracle(ours, pyr, coords, radius)


@pytest.mark.parametrize("radius", [3, 4])
@BOTH_FORMS
def test_lookup_grad_wrt_levels_matches_gather_oracle(hw, radius):
    """train.py differentiates the lookup with respect to the volume only
    (coords are stop_gradient-ed per iteration): the gather's scatter-add
    and the contraction's transposes must deposit the same cotangent."""
    B, (H, W) = 1, hw
    pyr = _random_pyramid(7, B, H, W, differentiated=True)
    coords = _jittered_coords(8, B, H, W, 5)
    cot = jnp.asarray(
        np.random.default_rng(10)
        .standard_normal((B, H, W, 4 * (2 * radius + 1) ** 2))
        .astype(np.float32)
    )

    def loss(lookup):
        return lambda levels: jnp.sum(
            lookup(CorrPyramid(levels, pyr.query_hw), coords, radius) * cot
        )

    ours = jax.jit(jax.grad(loss(corr_lookup)))(pyr.levels)
    theirs = jax.jit(jax.grad(loss(gather_lookup_oracle)))(pyr.levels)
    for lvl, (g, t) in enumerate(zip(ours, theirs)):
        assert g.shape == pyr.levels[lvl].shape
        assert np.asarray(t).any()
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(t), atol=2e-6, rtol=1e-6
        )


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _lookup_eqns(ambient_precision, hw):
    """Every equation of ``corr_lookup``'s jaxpr (sub-jaxprs included),
    traced under an ambient ``jax_default_matmul_precision``."""
    pyr = _random_pyramid(9, 1, *hw)
    coords = coords_grid(1, *hw)
    with jax.default_matmul_precision(ambient_precision):
        closed = jax.make_jaxpr(lambda lv, c: corr_lookup(
            CorrPyramid(lv, pyr.query_hw), c, 4))(pyr.levels, coords)
    return list(_walk(closed.jaxpr))


@pytest.mark.parametrize("ambient", ["bfloat16", "float32"])
@BOTH_FORMS
def test_lookup_has_no_gather_and_pins_its_own_precision(hw, ambient):
    """Structure, not speed: no gather is left in the volume lookup, and
    its interpolation is float32 arithmetic whatever matmul precision the
    caller's process runs at (jax's TPU default is one bf16 pass) — any
    ``dot_general`` in it carries HIGHEST and a float32 result itself."""
    eqns = _lookup_eqns(ambient, hw)
    names = [e.primitive.name for e in eqns]
    assert not set(names) & {
        "gather", "dynamic_slice", "scatter", "scatter-add",
        "conv_general_dilated",
    }
    # One matmul, for the x axis of the 128-wide level, and only there.
    assert names.count("dot_general") == (1 if hw[1] % 128 == 0 else 0)
    for e in eqns:
        if e.primitive.name == "dot_general":
            assert e.params["precision"] is not None
            assert set(jax.tree.leaves(e.params["precision"])) == {
                jax.lax.Precision.HIGHEST
            }
            assert e.params["preferred_element_type"] == jnp.float32


def _parent_lookup(pyramid, coords, radius):
    """``corr_lookup`` as it stood before the forward-only contraction (PR
    42), statement for statement: what a program that differentiates its
    lookup, and any program over float32 levels, must still trace."""
    from raft_ncup_tpu.ops.corr import _axis_weights, _window_contract

    B, H, W, _ = coords.shape
    K = 2 * radius + 1
    out = []
    for lvl, corr in enumerate(pyramid.levels):
        _, _, Hl, Wl = corr.shape
        wdt = jnp.promote_types(corr.dtype, coords.dtype)
        centre = coords.reshape(B, H * W, 2).astype(wdt) / (2**lvl)
        ax = _axis_weights(centre[..., 0], Wl, radius)
        ay = _axis_weights(centre[..., 1], Hl, radius)
        win = _window_contract(corr.astype(wdt), ax, ay)
        out.append(win.reshape(B, H, W, K * K))
    return jnp.concatenate(out, axis=-1)


# (level rows x columns, stored dtype) -> the form a forward-only program
# contracts it in (``ops/corr.py::contract_form``): odd sizes, a width that
# fills whole lanes and widths that do not, both sides of
# ``TAP_SUMS_MIN_SIZE`` (7x40 = 280 elements, 9x24 = 216).
FORWARD_ONLY_FORMS = {
    ((27, 64), jnp.bfloat16): "tap_sums",
    ((13, 32), jnp.bfloat16): "tap_sums",
    ((7, 40), jnp.bfloat16): "tap_sums",
    ((9, 24), jnp.bfloat16): "multiply_reduce",
    ((5, 128), jnp.bfloat16): "dot",
    ((27, 64), jnp.float32): "multiply_reduce",
    ((5, 128), jnp.float32): "dot",
}


@pytest.mark.parametrize(
    "level_hw,stored", list(FORWARD_ONLY_FORMS),
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else jnp.dtype(v).name,
)
def test_forward_only_contraction_is_the_parents_sums(level_hw, stored):
    """A level stored narrower than the coordinates is contracted, in a
    program that is not differentiated, a tap at a time in one pass over
    the level as stored (``_tap_sums``): the same float32 products and
    sums as ``_window_contract``'s, to the rounding of another order of
    addition, centres outside the level included. A float32 level, a level
    under the size, and every level of a pyramid built for a program that
    differentiates its lookup trace the parent's jaxpr; differentiating the
    forward-only form all the same gives the parent's gradient."""
    from raft_ncup_tpu.ops import corr

    (Hl, Wl), radius, (h, w) = level_hw, 4, (3, 5)
    rng = np.random.default_rng(11)
    levels = (
        jnp.asarray(rng.standard_normal((2, h * w, Hl, Wl)).astype(np.float32)).astype(stored),
    )
    xy = np.stack([
        rng.uniform(-radius - 3.0, Wl + radius + 3.0, (2, h, w)),
        rng.uniform(-radius - 3.0, Hl + radius + 3.0, (2, h, w)),
    ], axis=-1).astype(np.float32)
    xy[0, 0, 0], xy[0, 0, 1] = (-80.0, -80.0), (1e6, -1e6)  # no tap inside
    xy[0, 0, 2] = (Wl - 0.5, Hl - 0.5)  # the far corner
    coords = jnp.asarray(xy)
    form = FORWARD_ONLY_FORMS[level_hw, stored]

    def plain(lv, c):
        return corr.corr_lookup(CorrPyramid(lv, (h, w)), c, radius)

    def training(lv, c):
        return corr.corr_lookup(DifferentiatedCorrPyramid(lv, (h, w)), c, radius)

    def parent(lv, c):
        return _parent_lookup(CorrPyramid(lv, (h, w)), c, radius)

    corr.reset_contract_forms()
    traced = jax.make_jaxpr(plain)(levels, coords)
    assert corr.contract_forms() == {"level0": f"{form}/{jnp.dtype(stored).name}"}
    theirs = jax.make_jaxpr(parent)(levels, coords)
    assert str(jax.make_jaxpr(training)(levels, coords)) == str(theirs)
    recorded = corr.contract_forms()["level0"]
    assert not recorded.startswith("tap_sums")
    assert (str(traced) == str(theirs)) == (form != "tap_sums")
    # float32 arithmetic, whatever the level is stored in
    for eqn in _walk(traced.jaxpr):
        if eqn.primitive.name in ("mul", "add", "reduce", "reduce_sum", "dot_general"):
            assert {v.aval.dtype for v in eqn.invars} <= {jnp.dtype(jnp.float32)}, eqn

    ours, want = jax.jit(plain)(levels, coords), jax.jit(parent)(levels, coords)
    assert ours.dtype == jnp.float32 and np.asarray(want).any()
    assert not np.asarray(ours)[0, 0, :2].any()  # padding_mode='zeros'
    np.testing.assert_allclose(np.asarray(ours), np.asarray(want), atol=2e-6, rtol=0)
    if form == "tap_sums":
        cot = jnp.asarray(rng.standard_normal(want.shape).astype(np.float32))
        g_ours, g_want = (
            jax.jit(jax.grad(lambda lv: jnp.sum(f(lv, coords) * cot)))(levels)[0]
            for f in (plain, parent)
        )
        assert g_ours.dtype == stored
        np.testing.assert_array_equal(
            np.asarray(g_ours.astype(jnp.float32)), np.asarray(g_want.astype(jnp.float32))
        )
