"""All-pairs correlation volume + multi-scale windowed lookup.

Two interchangeable implementations behind one signature:

- ``build_corr_pyramid`` + ``corr_lookup`` materialize the O((HW)^2) volume
  once per pair (reference semantics: core/corr.py:13-44). The einsum maps
  straight onto the MXU; the 4-level pyramid is built with 2x2 average
  pooling. Fast at training resolutions; the volume at 1/8 res of a 400x720
  crop is ~100 MB/pair in fp32.

  The lookup reads the volume densely and selects with arithmetic: the
  (2r+1)^2 bilinear taps of a query share one centre, so they are
  ``Ay @ V[q] @ Ax^T`` with per-axis weight matrices of two non-zeros a
  row. A scalar gather of the same taps fetched 0.05% of the volume and
  took 40-80x as long as this on a TPU v5e (root PERF.md section 6, PR 25:
  at 8 x 55x128 queries the four levels cost 394 ms as gathers, 9.4 ms as
  multiply + reduce, 5.0 ms with level 0's x axis on the MXU). The x-first
  matmul form won only where the level's width fills whole lanes (55x128:
  5.3 against 9.9 ms; 27x64, 46x96, 68x120, 136x240: 1.1-2.6x slower), so
  ``_window_contract`` chooses by that.

  The multiply + reduce form is bound by the bytes of the level, and how
  often it reads them the compiler decides from the level's WIDTH. It lays
  a level out queries in lanes; where the width is whole sublane tiles
  (27x64, 47x160) x goes to the sublanes and the first stage takes all K
  taps in one walk of the level; at a width that is not (KITTI's 47x156, 8
  x 7,332 queries) the batch goes there, the taps are cut into three
  blocks of three and the 1.72 GB level is walked once a block
  (``output_window_bounds`` [3,47,6,1,1] against [1,9,47,2,1]; its own
  ``estimated_cycles`` do not tell the two apart): 7.97 ms an iteration at
  level 0 and 2.16 at level 1 where 47x160 and 23x80 take 3.34 and 0.95,
  the four levels 16.9 against 10.2 (root PERF.md section 6, PR 47, which
  lands what PR 46 measured). So a
  refinement loop that is not differentiated STORES a level whose width is
  no multiple of 8 at the next one (``build_loop_pyramid``,
  ``stored_width``: zero columns, +2.6% of level 0), once a pair where it
  is built. Level 0 stored 256 wide and sent to the MXU like Sintel's read
  6.11 ms for 3.34 + 1.81 and asks 3 GiB more of the device.
  The training step's pyramid keeps every level's own width: its backward
  lays the cotangent sums out anew with every change to a level (PERF.md
  section 5).

  A program that is not differentiated contracts a narrower level that is
  STORED narrow (bfloat16 under ``bf16_infer``) in a third order of the
  same float32 sums, ``_tap_sums``: the multiply + reduce form pays a pass
  of its own to widen such a level (the compiler fuses no producer into a
  reduction over a broadcast, which reuses every element of the level K
  times; 27x64 bfloat16 at batch 16: 6.1 ms an iteration where float32 at
  batch 8 pays 1.8), and one reduction of K operands, a product with each
  tap's weights, reuses nothing, reads the stored level once and widens it
  in the pass: 2.7 ms; 13x32 1.9 -> 1.2; 6x16 0.74 -> 0.72, hence
  ``TAP_SUMS_MIN_SIZE`` (root PERF.md section 6, PR 41-42). The training
  step asks for the two forms above alone
  (``build_corr_pyramid(differentiated=True)``): it traces its lookup
  three times over, and its start pays for every form
  (``contract_form``).

- ``corr_lookup_onthefly`` never materializes the volume. Because the
  lookup bilinearly samples the volume over its *second* pair of spatial
  dims for a fixed query pixel, and correlation is linear in fmap2,
  sample-then-dot == dot-then-sample:

      bilerp_q <f1(p), f2(q)> = <f1(p), bilerp_q f2(q)>

  (zero padding also agrees: an out-of-bounds tap contributes 0 either
  way). So we bilinearly sample fmap2 at the 81 window taps and contract
  with fmap1 on the fly, chunked over query rows to bound memory. This is
  the memory-efficient path for 1080p / 32-iter inference where the full
  volume would be several GB (SURVEY.md §5 "long-context" analogue).

A fused Pallas kernel for the lookup lives in
``raft_ncup_tpu.ops.corr_pallas`` and is validated against these.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from raft_ncup_tpu.ops.geometry import avg_pool2, grid_sample
from raft_ncup_tpu.precision.sites import record_site
from raft_ncup_tpu.utils.knobs import knob_positive_int

ROW_CHUNK_ENV = "RAFT_NCUP_CORR_ROW_CHUNK"
_DEFAULT_ROW_CHUNK = 8


def effective_row_chunk() -> int:
    """The row-chunk size ``corr_lookup_onthefly`` traces with when the
    caller passes none: the ``RAFT_NCUP_CORR_ROW_CHUNK`` override if
    set (a tuning knob — larger chunks amortize the scan at more peak
    memory; the 4K fallback/sharded paths are where it matters), else
    8. Recorded in the cost-ledger meta (:func:`corr_tuning_meta`) so
    the choice behind a warmed executable is visible to
    ``scripts/flip_recommendations.py`` and ROADMAP item 1's
    autotuner."""
    return knob_positive_int(ROW_CHUNK_ENV) or _DEFAULT_ROW_CHUNK


def corr_tuning_meta() -> dict:
    """Effective correlation tuning-knob values — one flat dict the
    compiled-executable cost ledger (inference/costs.py) stamps into
    every forward/metric entry's meta: the onthefly ``row_chunk`` plus
    the Pallas kernel's query-block / band-rows knobs
    (``ops.corr_pallas.tuning_meta``). The autotuner's sweep surface:
    persisted next to the XLA cost facts, keyed like the executables."""
    meta = {"corr_row_chunk": effective_row_chunk()}
    try:
        from raft_ncup_tpu.ops import corr_pallas

        meta.update(corr_pallas.tuning_meta())
    except ImportError:  # pragma: no cover - jax builds without pallas
        pass
    return meta


class CorrPyramid(NamedTuple):
    """Materialized correlation pyramid.

    ``levels[l]`` has shape (B, H1*W1, H2/2^l, W2/2^l): all-pairs
    correlation between every query pixel of fmap1 and the (pooled) pixels
    of fmap2, pre-divided by sqrt(dim) (reference: core/corr.py:47-55);
    from :func:`build_loop_pyramid`, zero columns after those up to
    :func:`stored_width`.
    """

    levels: tuple[jax.Array, ...]
    query_hw: tuple[int, int]


class DifferentiatedCorrPyramid(CorrPyramid):
    """The pyramid of a program that takes gradients through its lookup
    (the training step; ``build_corr_pyramid(differentiated=True)``):
    :func:`corr_lookup` contracts it in the two forms of
    :func:`_window_contract` alone (:func:`contract_form`). A type and not
    a field or an argument of the lookup: it stays what it is through
    ``jit``, ``scan`` and ``jax.checkpoint``, where a flag in the tuple
    would become an array, and the lookup keeps its three arguments for
    whoever calls, wraps or replaces it."""

    __slots__ = ()


def _delta_window(radius: int, dtype=jnp.float32) -> jax.Array:
    """(K, K, 2) window offsets, K = 2r+1.

    Tap (i, j) offsets the *x* coordinate by (i - r) and the *y* coordinate
    by (j - r): the reference builds ``delta`` from ``meshgrid(dy, dx)`` and
    adds it to (x, y)-ordered centroids (core/corr.py:31-37), so the first
    window axis varies the x offset. Preserving this ordering keeps the
    lookup's output channel order — and therefore motion-encoder weights —
    compatible with reference checkpoints.
    """
    d = jnp.arange(-radius, radius + 1, dtype=dtype)
    di, dj = jnp.meshgrid(d, d, indexing="ij")
    return jnp.stack([di, dj], axis=-1)  # [..., 0] -> x offset, [..., 1] -> y


def _zero_columns(x: jax.Array, axis: int, width: int) -> jax.Array:
    """``x`` with zeros after its ``axis`` up to ``width``; ``x`` itself,
    and no ``pad`` in the trace, where it is that wide already."""
    if x.shape[axis] == width:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, width - x.shape[axis])
    return jnp.pad(x, pads)


def _build_levels(fmap1, fmap2, num_levels: int, dtype, stored: Sequence[int]) -> tuple:
    """The volume of (B, H, W, C) feature maps and its average pyramid,
    level ``l`` (``W >> l`` columns of its own) with zero columns on its
    right up to ``stored[l]``. Level 0's are zero FEATURE columns of
    ``fmap2``, so the product writes the level at its stored width and no
    second copy of it exists; a pooled level is pooled from the columns
    that are the level's own (an odd width pools VALID: 153 -> 76, no
    half-weight column) and padded after."""
    B, H, W, C = fmap1.shape
    f1 = fmap1.reshape(B, H * W, C).astype(dtype)
    f2 = _zero_columns(fmap2.astype(dtype), 2, stored[0])
    f2 = f2.reshape(B, H * stored[0], C)
    record_site("corr_pyramid/volume", dtype, jnp.float32)
    corr = jnp.einsum(
        "bxc,byc->bxy", f1, f2, preferred_element_type=jnp.float32
    ) / math.sqrt(C)
    corr = corr.astype(dtype).reshape(B, H * W, H, stored[0])

    levels = [corr]
    for lvl in range(1, num_levels):
        own = levels[-1]
        if own.shape[3] != W >> (lvl - 1):
            own = own[..., : W >> (lvl - 1)]
        n, q, h, w = own.shape
        pooled = avg_pool2(own.reshape(n * q, h, w, 1))
        pooled = pooled.reshape(n, q, pooled.shape[1], pooled.shape[2])
        levels.append(_zero_columns(pooled, 3, stored[lvl]))
    return tuple(levels)


def build_corr_pyramid(
    fmap1: jax.Array, fmap2: jax.Array, num_levels: int = 4, dtype=None,
    differentiated: bool = False,
) -> CorrPyramid:
    """Compute the all-pairs correlation volume and its average pyramid,
    every level at its own width.

    Args:
      fmap1, fmap2: (B, H, W, C) feature maps (cast to ``dtype``, default
        float32 like the reference's ``fmap.float()`` at
        core/raft.py:103-104).
      dtype: storage dtype of the volume — the dominant memory term, so
        the precision policy's bf16 presets halve it here
        (``PrecisionPolicy.corr_jnp``). The dot products ACCUMULATE in
        f32 regardless (``preferred_element_type``); only storage
        narrows. ``corr_lookup`` widens the level again before its
        arithmetic, so coordinates never demote.
      differentiated: the program takes gradients through the lookups of
        this pyramid (:class:`DifferentiatedCorrPyramid`).
    """
    W = fmap1.shape[2]
    levels = _build_levels(
        fmap1, fmap2, num_levels, dtype or jnp.float32,
        [W >> lvl for lvl in range(num_levels)],
    )
    kind = DifferentiatedCorrPyramid if differentiated else CorrPyramid
    return kind(levels=levels, query_hw=fmap1.shape[1:3])


def stored_width(wl: int) -> int:
    """The width a refinement loop that is not differentiated stores a
    level ``wl`` columns wide at: the next multiple of the TPU's 8
    sublanes (timings and the compiler's schedules: module docstring). The
    rule reads the shape alone: a multiple of 8 (of 128 among them, the
    ``dot`` form's) is stored as it is."""
    return -(-wl // _SUBLANES) * _SUBLANES


def build_loop_pyramid(
    fmap1: jax.Array, fmap2: jax.Array, num_levels: int = 4, dtype=None,
    differentiated: bool = False,
) -> CorrPyramid:
    """:func:`build_corr_pyramid` for a refinement loop, which looks its
    pyramid up every iteration. One that takes gradients through its
    lookups (``differentiated``) gets that very pyramid: its backward lays
    the cotangent sums out anew with every change to a level. Any other
    gets each level :func:`stored_width` wide, zero columns on its right,
    which the lookup's weights meet as they meet ``padding_mode='zeros'``
    (a tap that lands there is a weight on a zero: the same sums plus exact
    zeros); where every width is a multiple of 8 already (Sintel's 128 / 64
    / 32 / 16) that is :func:`build_corr_pyramid`'s trace, letter for
    letter. A builder of its own and not the other's default: what reads
    ``levels[l]`` as the level itself (the benchmark's site checks, the
    tests' oracles) builds the other."""
    if differentiated:
        return build_corr_pyramid(fmap1, fmap2, num_levels, dtype, True)
    W = fmap1.shape[2]
    stored = [stored_width(W >> lvl) for lvl in range(num_levels)]
    levels = _build_levels(fmap1, fmap2, num_levels, dtype or jnp.float32, stored)
    _stored_widths.update({
        f"level{lvl}": s for lvl, s in enumerate(stored) if s != W >> lvl
    })
    return CorrPyramid(levels=levels, query_hw=fmap1.shape[1:3])


def _tap_row(t: jax.Array, size: int) -> jax.Array:
    """Bilinear weights along one axis of taps at ``t`` ``(...)``: rows
    ``(..., size)`` holding ``1 - d`` at position ``floor(t)`` and
    ``d = t - floor(t)`` at ``floor(t) + 1`` — the very numbers
    ``grid_sample`` multiplies its corner taps by — and zeros elsewhere. A
    corner outside ``[0, size)`` matches no position, which is
    ``padding_mode='zeros'`` without a mask."""
    t0 = jnp.floor(t)
    d = (t - t0)[..., None]
    t0 = t0[..., None]
    pos = jnp.arange(size, dtype=t.dtype)
    return jnp.where(pos == t0, 1.0 - d, 0.0) + jnp.where(
        pos == t0 + 1.0, d, 0.0
    )


def _axis_weights(centre: jax.Array, size: int, radius: int) -> jax.Array:
    """:func:`_tap_row` of the K = 2r+1 window taps along one axis: window
    centres ``(...)`` -> ``(..., K, size)``; tap ``k`` sits at
    ``centre + k - r``."""
    taps = jnp.arange(-radius, radius + 1, dtype=centre.dtype)
    return _tap_row(centre[..., None] + taps, size)


# The TPU's lane width: a level whose row fills whole lanes contracts its
# x axis on the MXU (``_window_contract``).
_LANES = 128
# Its sublane count: a refinement loop that is not differentiated stores a
# level of any other width at the next multiple of it (``stored_width``).
_SUBLANES = 8

# The smallest level (elements a query) that takes the tap sums (13x32
# gains, 6x16 does not: module docstring), and the rows of it that keep the
# multiply + reduce form (``_tap_sums``).
TAP_SUMS_MIN_SIZE = 256
_HEAD_ROWS = 2

# Which form each level of the last traced lookup took: a trace-time tally
# in the manner of ``precision/sites.py`` (reset before a program is
# lowered and read after it: ``inference/costs.build_and_record``).
_contract_forms: dict[str, tuple[str, str]] = {}
# The stored width of each level that a ``build_loop_pyramid`` traced since
# the last reset stored wider than the level's own.
_stored_widths: dict[str, int] = {}


def reset_contract_forms() -> None:
    _contract_forms.clear()
    _stored_widths.clear()


def contract_forms() -> dict:
    """``{"level0": "<form>/<stored dtype>", ...}`` of the ``volume`` lookup
    traced since the last reset (:func:`contract_form` names the forms),
    ``"<form>@<stored width>/<stored dtype>"`` for a level the pyramid
    traced since then stores wider than its own (:func:`stored_width`);
    empty where the program has none (the ``onthefly`` and ``pallas``
    paths)."""
    return {
        level: f"{form}@{_stored_widths[level]}/{stored}"
        if level in _stored_widths else f"{form}/{stored}"
        for level, (form, stored) in sorted(_contract_forms.items())
    }


def contract_form(level_hw, stored, computed, differentiated: bool) -> str:
    """How a level of this shape (as it is STORED: :func:`build_loop_pyramid`
    has made a width that misfits the multiply + reduce form a multiple of
    8 by now, except in a differentiated pyramid), stored as ``stored`` and
    contracted in ``computed``, is contracted (timings: module docstring):

    - ``"dot"``: a row that fills whole lanes takes its x axis to the MXU;
      the compiler widens a narrow level inside that fusion.
    - ``"tap_sums"``: in a program that is not differentiated, a narrower
      level stored narrow, of :data:`TAP_SUMS_MIN_SIZE` elements or more,
      is summed a tap at a time, all taps in one pass over the level as
      stored (:func:`_tap_sums`).
    - ``"multiply_reduce"``: everything else, y first then x.
    """
    hl, wl = level_hw
    if wl % _LANES == 0:
        return "dot"
    narrow = jnp.dtype(stored).itemsize < jnp.dtype(computed).itemsize
    if (
        narrow and not differentiated
        and hl > _HEAD_ROWS and hl * wl >= TAP_SUMS_MIN_SIZE
    ):
        return "tap_sums"
    return "multiply_reduce"


def _contract_y(cols: jax.Array, ay: jax.Array) -> jax.Array:
    """``cols`` (..., Hl, K_x), a level's rows already contracted over x,
    with ``ay`` (..., K_y, Hl) -> (..., K_x, K_y)."""
    ay_t = jnp.swapaxes(ay, -1, -2)  # (..., Hl, K_y)
    return jnp.sum(cols[..., :, :, None] * ay_t[..., :, None, :], axis=-3)


def _window_contract(vol: jax.Array, ax: jax.Array, ay: jax.Array) -> jax.Array:
    """``out[..., i, j] = sum_y sum_x ay[..., j, y] vol[..., y, x] ax[..., i, x]``
    for ``vol`` (..., Hl, Wl), ``ax`` (..., K, Wl), ``ay`` (..., K, Hl).

    Two orders of the same sums, chosen from the level's width (timings:
    module docstring). Both are float32 arithmetic under any ambient
    ``jax_default_matmul_precision``: the dot pins HIGHEST itself, the
    multiply + reduce forms never were matmuls.
    """
    if vol.shape[-1] % _LANES == 0:
        # x first, one small matmul per query: (Hl, Wl) @ (Wl, K).
        cols = jnp.einsum(
            "...yx,...ix->...yi", vol, ax,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=vol.dtype,
        )  # (..., Hl, K_x)
        return _contract_y(cols, ay)
    # y first: y is the major axis of the layout XLA gives the volume, so
    # this is an accumulation of whole rows; then x over the K rows left.
    rows = jnp.sum(ay[..., :, :, None] * vol[..., None, :, :], axis=-2)
    return jnp.sum(ax[..., :, None, :] * rows[..., None, :, :], axis=-1)


def _sum_each(operands: Sequence[jax.Array], axis: int) -> tuple:
    """``operand.sum(axis)`` of each operand, as ONE reduction of them all:
    the compiler reads what the operands share once, and fuses a widening
    of it into that pass, which it refuses a ``sum`` over a broadcast (a
    product of the level with all K taps' weights reuses every element of
    the level K times, and a producer is not fused into such a consumer)."""
    axis %= operands[0].ndim
    zeros = tuple(jnp.zeros((), x.dtype) for x in operands)
    return tuple(jax.lax.reduce(
        tuple(operands), zeros,
        lambda a, b: tuple(x + y for x, y in zip(a, b)), (axis,),
    ))


def _tap_sums(vol: jax.Array, centre: jax.Array, radius: int, wdt) -> jax.Array:
    """:func:`_window_contract`'s y-first sums over a level ``vol`` (..., Hl,
    Wl) as stored, narrower than ``wdt``, around ``centre`` (..., 2) as (x,
    y): one product of the widened level with each tap's row of weights and
    the K row sums in one pass (:func:`_sum_each`), then x the same way
    over the K rows left. Every product and every sum is ``wdt``
    arithmetic; nothing is rounded that the other form does not round.

    The first :data:`_HEAD_ROWS` rows are contracted apart, x first, as a
    multiply + reduce. They are two rows' work, and they decide how the
    whole level is stored: the compiler lays a level out for a multiply +
    reduce that reads it (queries in the lanes, no padding), and for a
    reduction of several operands alone it keeps the rows-in-lanes layout
    the pooling wrote, which pads a 64-wide row to 128 lanes and was
    slower than what this form replaces (root PERF.md section 6, PR 41).

    jax does not transpose a reduction of several operands, and a program
    that differentiates its lookup says so where it builds its pyramid
    (:class:`DifferentiatedCorrPyramid`). One that differentiates this all
    the same gets :func:`_window_contract`'s tangents, and its sums with
    them."""
    Hl, Wl = vol.shape[-2:]

    def plain(vol, centre):
        return _window_contract(
            vol.astype(wdt), _axis_weights(centre[..., 0], Wl, radius),
            _axis_weights(centre[..., 1], Hl, radius),
        )

    @jax.custom_jvp
    def sums(vol, centre):
        ax = _axis_weights(centre[..., 0], Wl, radius)  # (..., K_x, Wl)
        body = vol[..., _HEAD_ROWS:, :].astype(wdt)
        # Each tap's row of weights is made where it is used: a slice of
        # the stacked rows is a copy of all of them first.
        rows = _sum_each([
            _tap_row(centre[..., 1] + tap, Hl)[..., _HEAD_ROWS:][..., None] * body
            for tap in range(-radius, radius + 1)
        ], -2)  # K_y x (..., Wl)
        out = jnp.stack(
            _sum_each([ax * row[..., None, :] for row in rows], -1), axis=-1
        )  # (..., K_x, K_y)
        head = vol[..., :_HEAD_ROWS, :].astype(wdt)
        cols = jnp.sum(head[..., :, None, :] * ax[..., None, :, :], axis=-1)
        return out + _contract_y(
            cols, _axis_weights(centre[..., 1], _HEAD_ROWS, radius)
        )

    sums.defjvp(lambda primals, tangents: jax.jvp(plain, primals, tangents))
    return sums(vol, centre)


def corr_lookup(pyramid: CorrPyramid, coords: jax.Array, radius: int) -> jax.Array:
    """Sample (2r+1)^2 windows around ``coords / 2^l`` at every level.

    Reference: core/corr.py:23-44. Gather-free: all K*K taps of a query
    sit at integer offsets from one centre, so its bilinear samples
    factorise per axis into a contraction of the query's whole level with
    two small weight matrices (:func:`_axis_weights`,
    :func:`_window_contract`). Every element of the level may contribute;
    the selection is arithmetic.

    Args:
      pyramid: from :func:`build_corr_pyramid` or, a level's zero columns
        reading as the outside of the level, :func:`build_loop_pyramid`. One
        built for a program
        that differentiates its lookup (the training step) keeps to the two
        forms of :func:`_window_contract`, whose trace a step pays three
        times over at every start; any other may take :func:`_tap_sums` too
        (:func:`contract_form`).
      coords: (B, H, W, 2) query positions in fmap2 pixel coordinates.
    Returns:
      (B, H, W, L * (2r+1)^2) at the promoted (volume, coords) dtype —
      float32 whenever coords are f32 (the policy's coord contract),
      level-major then window-tap order, the first window axis
      offsetting x (:func:`_delta_window`).
    """
    B, H, W, _ = coords.shape
    K = 2 * radius + 1
    differentiated = isinstance(pyramid, DifferentiatedCorrPyramid)

    out = []
    for lvl, corr in enumerate(pyramid.levels):
        _, _, Hl, Wl = corr.shape
        # A narrow-storage volume (bf16 under the precision policy) is
        # widened for its contraction; the coordinates are never narrowed.
        wdt = jnp.promote_types(corr.dtype, coords.dtype)
        centre = coords.reshape(B, H * W, 2).astype(wdt) / (2**lvl)
        record_site(f"level{lvl}", wdt)
        form = contract_form((Hl, Wl), corr.dtype, wdt, differentiated)
        _contract_forms[f"level{lvl}"] = (form, str(corr.dtype))
        if form == "tap_sums":
            win = _tap_sums(corr, centre, radius, wdt)
        else:
            ax = _axis_weights(centre[..., 0], Wl, radius)  # (B, HW, K, Wl)
            ay = _axis_weights(centre[..., 1], Hl, radius)  # (B, HW, K, Hl)
            win = _window_contract(corr.astype(wdt), ax, ay)
        # win: (B, HW, K_x, K_y)
        out.append(win.reshape(B, H, W, K * K))
    return jnp.concatenate(out, axis=-1)


def _pool_fmap_pyramid(fmap2: jax.Array, num_levels: int) -> list[jax.Array]:
    """Average-pool fmap2 into a pyramid.

    Pooling the *features* then correlating equals pooling the correlation
    volume (reference pools the volume, core/corr.py:19-21) because the
    2x2 mean acts on the fmap2 axes only and correlation is linear in
    fmap2.
    """
    levels = [fmap2]
    for _ in range(num_levels - 1):
        levels.append(avg_pool2(levels[-1]))
    return levels


def corr_lookup_onthefly(
    fmap1: jax.Array,
    fmap2: jax.Array,
    coords: jax.Array,
    radius: int,
    num_levels: int = 4,
    row_chunk: int | None = None,
    levels: Sequence[int] | None = None,
    dtype=None,
) -> jax.Array:
    """Windowed correlation lookup without materializing the volume.

    Equivalent to ``corr_lookup(build_corr_pyramid(f1, f2), coords, r)`` up
    to float associativity; O(B * HW * L * K^2 * C) compute per call but
    O(B * row_chunk * W * K^2 * C) peak memory.

    Args:
      fmap1, fmap2: (B, H, W, C).
      coords: (B, H, W, 2).
      row_chunk: query rows processed per scan step (H % row_chunk may be
        nonzero; handled by padding). ``None`` (default) resolves via
        :func:`effective_row_chunk` — 8, overridable with
        ``RAFT_NCUP_CORR_ROW_CHUNK`` (the knob that tunes the 4K
        fallback path; its value rides the cost-ledger meta).
      levels: pyramid level indices to compute (default: all
        ``num_levels``); the Pallas dispatcher uses this to source only
        the levels whose slab exceeds its VMEM budget.
      dtype: feature/pyramid dtype (default f32; the precision policy's
        ``corr_jnp`` under bf16 presets — halves the resident pyramid).
        The tap sampling promotes back through the f32 coords and the
        contraction accumulates in f32, so the output stays f32.
    """
    B, H, W, C = fmap1.shape
    K = 2 * radius + 1
    scale = 1.0 / math.sqrt(C)
    dtype = dtype or jnp.float32
    if row_chunk is None:
        row_chunk = effective_row_chunk()
    level_ids = tuple(range(num_levels)) if levels is None else tuple(levels)
    f2_levels = _pool_fmap_pyramid(fmap2.astype(dtype), num_levels)
    f1 = fmap1.astype(dtype)
    delta = _delta_window(radius)

    pad_rows = (-H) % row_chunk
    f1p = jnp.pad(f1, ((0, 0), (0, pad_rows), (0, 0), (0, 0)))
    cp = jnp.pad(coords.astype(jnp.float32), ((0, 0), (0, pad_rows), (0, 0), (0, 0)))
    n_chunks = (H + pad_rows) // row_chunk

    f1c = f1p.reshape(B, n_chunks, row_chunk, W, C).transpose(1, 0, 2, 3, 4)
    cc = cp.reshape(B, n_chunks, row_chunk, W, 2).transpose(1, 0, 2, 3, 4)

    def chunk_fn(carry, xs):
        f1_chunk, coords_chunk = xs  # (B, rc, W, C), (B, rc, W, 2)
        per_level = []
        for lvl in level_ids:
            centroid = coords_chunk[:, :, :, None, None, :] / (2**lvl)
            taps = centroid + delta[None, None, None]  # (B, rc, W, K, K, 2)
            sampled = grid_sample(f2_levels[lvl], taps)  # (B, rc, W, K, K, C)
            record_site(f"onthefly/level{lvl}", sampled.dtype, jnp.float32)
            corr = jnp.einsum(
                "brwijc,brwc->brwij", sampled, f1_chunk,
                preferred_element_type=jnp.float32,
            ) * scale
            per_level.append(corr.reshape(*corr.shape[:3], K * K))
        return carry, jnp.concatenate(per_level, axis=-1)

    _, chunks = jax.lax.scan(chunk_fn, None, (f1c, cc))
    # (n_chunks, B, rc, W, L*K*K) -> (B, H, W, L*K*K)
    out = chunks.transpose(1, 0, 2, 3, 4).reshape(B, H + pad_rows, W, -1)
    return out[:, :H]
