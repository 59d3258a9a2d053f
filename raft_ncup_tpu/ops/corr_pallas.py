"""Pallas TPU kernel: fused, volume-free correlation-window lookup.

The XLA paths (raft_ncup_tpu.ops.corr) either materialize the O((HW)^2)
all-pairs volume (`volume`) or bilinearly gather fmap2 taps (`onthefly`).
This kernel fuses the per-level dot product INTO the windowed lookup, so
the volume never exists anywhere — the §2a(a) design from SURVEY.md:

- Every tap of a query's (2r+1)^2 window shares the same fractional
  offset: the window is an integer-aligned grid shifted by one sub-pixel
  amount, so the whole K x K window equals a 2 x 2 bilinear blend of a
  (K+1) x (K+1) integer-aligned patch of correlations.
- That patch is `sum_c f1[q, c] * f2[iy : iy+K+1, ix : ix+K+1, c]` — a
  dynamic-start slice of the VMEM-resident fmap2 level followed by a
  lane reduction. No gather, and HBM traffic is fmap2 once per query
  block instead of a volume pass. (Mosaic takes a dynamic start on the
  sublane-tiled column dimension only when it is provably tile-aligned,
  so the slice is the aligned window holding the patch; the shift by
  the residue is applied to the summed correlations, never to the
  C-channel data — :func:`_group_windows`.)

Kernel shape: queries are processed in GROUPS of 8, one body for both
tiers (:func:`_group_windows`), **reduce first, align afterwards**:

- Integer window origins are precomputed on the XLA side and shipped as
  an int32 array in SMEM (the Mosaic-idiomatic home for indices that
  drive dynamic slices); fractional offsets ride along in VMEM.
- Per query, the aligned (K+1, ``_patch_cols``, C) window is read
  straight from the slab, once: its columns past the fold width
  (``_fold_cols``, two float32 tiles) take the place of the columns
  before the residue (a select; a K+1-column patch never needs both),
  each row is multiplied by the query's feature row and summed over
  the lanes. The C-channel data is never rotated, sliced, stored or
  reloaded: a dynamic rotate of the 60-register window costs more than
  everything else in the body (PERF.md section 6, PR 36).
- The (fold,) sums of window row y land in lane ``g * 16 + y`` of a
  (fold, 128) array, columns on sublanes, and the residue shift is a
  sublane rotate of that one small array. The 8 queries' arrays have
  disjoint lanes and add up to one PACKED array of two registers, on
  which the 2x2 bilinear blend is four multiplies by per-lane weights;
  one transpose hands out the (G, K, K) windows for the store.
- What bounds the body now is the lane reduction itself, 20 a query
  (K+1 rows x 2 tiles) on the chip's three cross-lane units (PERF.md
  section 6, PR 36).

Zero-padding semantics (out-of-bounds taps contribute zero, matching
``grid_sample``) come from pre-padding each level with K+2 zeros per
side; window starts are clamped into the padded array, and any fully-OOB
window lands entirely inside the zero margin.

VMEM budget: the RESIDENT kernel keeps the whole padded level on-chip
next to the pipeline's block buffers. The budget is Mosaic's scoped
VMEM limit (16 MiB by default, stated to the compiler as
``vmem_limit_bytes``; override with RAFT_NCUP_VMEM_BYTES) and every
buffer is counted as Mosaic allocates it — last two dims padded to the
(8, 128) tile, pipelined blocks double-buffered (:func:`_block_bytes`):
the slab and the f1 / frac / out blocks, nothing else (the group body
works in registers). The chip's compiler refused the first, unpadded
count (20.2 MB asked of 16 MiB); tests/test_tpu_aot_compile.py keeps
gate and compiler in agreement at the flagship's widths.

Banded tier (round-15 redesign — the correlation memory wall,
ROADMAP item 4): levels whose padded slab exceeds the resident budget
no longer fall straight back to XLA. The level is split into horizontal
BANDS of ``band_rows`` origin rows; each program touches only its
band's slab plus a ``K+2``-row halo, sized by :func:`band_plan` so
``band_slab + query blocks`` fits the same ``fits_vmem``
budget at the policy itemsize. Mechanics:

- The zero-padded level stays in HBM (``memory_space=ANY``); one band
  slab of ``band_rows + K + 2`` rows is DMA'd into a single VMEM
  scratch (``pltpu.make_async_copy``) when the band changes — the slab
  is NOT double-buffered, which is exactly what lets a 4K level-0 band
  fit where a blocked operand's double buffer would not.
- Queries are assigned XLA-side to the band containing their clamped
  window origin (``ibase`` already computes it), stable-argsorted by
  band, and a per-(batch) chunk table — the (band, query-block,
  lo, hi, fresh-band) segments of the sorted query array, i.e. the
  ``(B, band, query_block)`` grid with its empty cells compressed out —
  ships as a scalar-prefetch operand in SMEM
  (``pltpu.PrefetchScalarGridSpec``) and drives every block index map.
- The kernel grid is ``(B, chunk)`` with a MASKED group loop: groups
  outside the chunk's ``[lo, hi)`` sorted-query range are skipped, and
  boundary groups accumulate masked contributions, so a query block
  straddling a band boundary is completed by its neighbouring chunks
  (consecutive out-block revisits — the sanctioned accumulation
  pattern). Out-of-band taps read the band's own zero/halo rows, so
  zero-padding semantics stay BITWISE identical to the resident kernel.

Dispatch is PER LEVEL and THREE-TIER: resident kernel -> banded kernel
-> XLA onthefly (counted separately in ``dispatch_counts``). At 1080p
f32, levels 0-1 (~42 MB / ~15.3 MB padded, both over the 0.9x resident
budget) now take the BANDED kernel and levels 2-3 the resident one; at
4K (2176x3840) every level qualifies for a kernel tier at f32 and bf16
(exact counts pinned by tests/test_pallas_lowering.py). The XLA
fallback remains only for band overrides that reject; on the TPU a
call whose every level falls back raises.

Tuning knobs (the first real surface for ROADMAP item 1's autotuner;
recorded in the cost-ledger meta via ``ops.corr.corr_tuning_meta``):
``RAFT_NCUP_CORR_QUERY_BLOCK`` (queries per block, default 128) and
``RAFT_NCUP_CORR_BAND_ROWS`` (band origin rows; default: largest that
fits the budget, multiple-of-8 preferred).

The kernel is forward-only; ``corr_lookup_pallas`` wraps it in a
``jax.custom_vjp`` whose backward runs the XLA on-the-fly path's VJP, so
the op stays trainable. (reference semantics: core/corr.py:23-44)
"""

from __future__ import annotations

import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from raft_ncup_tpu.utils.knobs import knob_positive_int
from raft_ncup_tpu.utils.runtime import VMEM_BYTES as _VMEM_BYTES

_QUERY_BLOCK = 128
_GROUP = 8  # queries per vectorized inner step (sublane tile)
# Lanes a query's K+1 window rows take in the group's packed array
# (_group_windows): the widest window a kernel tier takes.
_QUERY_LANES = 128 // _GROUP

# Scopes of the XLA work around the kernels (docs/OBSERVABILITY.md): the
# model's ``raft.corr_lookup`` holds the kernels and the output's
# transposes; these two split off the once-per-pair padded pyramid and
# the banded tier's per-iteration sort.
_PAD_SCOPE = "raft.corr_lookup.pad_levels"
_SORT_SCOPE = "raft.corr_lookup.band_sort"

QUERY_BLOCK_ENV = "RAFT_NCUP_CORR_QUERY_BLOCK"
BAND_ROWS_ENV = "RAFT_NCUP_CORR_BAND_ROWS"


def effective_query_block() -> int:
    """The query-block size both kernel tiers trace with: the
    ``RAFT_NCUP_CORR_QUERY_BLOCK`` override when set, else 128. A
    tuning knob (ROADMAP item 1): smaller blocks shrink the
    double-buffered block term of the VMEM budget, buying band rows."""
    return knob_positive_int(QUERY_BLOCK_ENV) or _QUERY_BLOCK


def band_rows_override() -> int | None:
    """``RAFT_NCUP_CORR_BAND_ROWS`` when set (an expert/autotuner knob:
    it wins over :func:`band_plan`'s budget-derived choice), else None
    = auto."""
    return knob_positive_int(BAND_ROWS_ENV)


def tuning_meta() -> dict:
    """The kernel's effective tuning-knob values, as recorded into the
    cost-ledger entry meta of every compiled executable
    (inference/costs.py) — the surface ROADMAP item 1's autotuner
    sweeps."""
    return {
        "corr_query_block": effective_query_block(),
        "corr_band_rows": band_rows_override() or "auto",
    }


# Trace-time per-level dispatch tally, mirroring ops.nconv: callers that
# label a measurement "corr=pallas" (chip_smoke.py) use this to tell which
# tier carried each pyramid level — resident kernel, banded kernel, or
# the XLA onthefly fallback (partial mixes are by design at large
# shapes and still count as the kernel running). Guarded by a lock:
# concurrent traces (two warmups on different threads) must not lose
# increments, even though a mixed tally is only interpretable under the
# single-thread discipline documented on dispatch_counts().
_counts_lock = threading.Lock()
_dispatch_counts = {
    "kernel": 0, "banded": 0, "fallback": 0, "levels_total": 0,
}


def reset_dispatch_counts() -> None:
    with _counts_lock:
        for k in _dispatch_counts:
            _dispatch_counts[k] = 0


def dispatch_counts() -> dict:
    """Copy of the per-level dispatch tally since the last reset.

    Three tier keys plus the denominator: ``kernel`` (whole level
    VMEM-resident), ``banded`` (level banded + DMA'd per band, see
    module docstring), ``fallback`` (XLA onthefly), and
    ``levels_total``. Counts trace-time decisions, one per pyramid
    level per TRACE — a custom_vjp backward trace, a shape-driven
    retrace, or a concurrent thread each add their own tallies, so the
    counts are only interpretable between a reset and a single lowering
    in a single thread, the discipline its callers follow (mutation
    itself is lock-guarded, so concurrent traces can't lose counts)."""
    with _counts_lock:
        return dict(_dispatch_counts)


def _count(tier: str, n: int = 1) -> None:
    with _counts_lock:
        _dispatch_counts[tier] += n


def _padded_hw(h: int, w: int, radius: int) -> tuple[int, int, int]:
    # A fully-OOB window is clamped to the array edge and must land
    # entirely inside the zero margin: K + 2 zeros per side.
    pad = 2 * radius + 3
    return h + 2 * pad, w + 2 * pad, pad


def _block_bytes(
    channels: int, radius: int, query_block: int, itemsize: int
) -> int:
    """Bytes of VMEM both kernel tiers need beside their slab, counted
    as Mosaic allocates them: the last two dims of every buffer padded
    to the (8, 128) tile, pipelined blocks double-buffered. The f1 block
    is at ``itemsize``; the frac block (Q, 2) and the out block
    (Q, K, K) are float32 — the out block's (9, 9) tail pads to
    (16, 128), which is most of the total and why the default query
    block is small. Nothing else: the group body
    (:func:`_group_windows`) keeps a query's window and the group's
    packed correlations in registers and has no scratch buffer."""
    K = 2 * radius + 1
    t8 = lambda n: -(-n // 8) * 8  # noqa: E731
    f1 = 2 * query_block * channels * itemsize
    frac = 2 * query_block * 128 * 4
    out = 2 * query_block * t8(K) * 128 * 4
    return f1 + frac + out


def _level_vmem_bytes(
    h: int,
    w: int,
    channels: int,
    radius: int,
    query_block: int | None = None,
    itemsize: int = 4,
) -> int:
    """Bytes of VMEM the kernel needs for one (h, w) level: the resident
    padded fmap2 slab + double-buffered query blocks, all at
    ``itemsize`` bytes per element (the precision policy's
    compute dtype — 2 under the bf16 presets, which is exactly the
    dispatch-threshold doubling ROADMAP item 3 wanted; the frac/out
    blocks stay f32 but are a few percent of the slab, so budgeting them
    at ``itemsize`` keeps the threshold ratio an exact itemsize ratio)."""
    if query_block is None:
        query_block = effective_query_block()
    hp, wp, _ = _padded_hw(h, w, radius)
    slab = hp * _alloc_width(wp, radius, itemsize) * channels
    return itemsize * slab + _block_bytes(
        channels, radius, query_block, itemsize
    )


def fits_vmem(
    h: int, w: int, channels: int, radius: int = 4, dtype=None
) -> bool:
    """Whether a (h, w, channels) fmap2 LEVEL fits the kernel's VMEM
    budget at ``dtype``'s element size (default float32). Dispatch
    inside :func:`corr_lookup_pallas` applies this per pyramid level at
    the precision policy's corr dtype — bf16 halves every per-level
    byte count, so levels rejected at f32 can stay on-chip; callers
    gating on the full-res shape get the level-0 answer."""
    itemsize = 4 if dtype is None else int(jnp.dtype(dtype).itemsize)
    return _level_vmem_bytes(
        h, w, channels, radius, itemsize=itemsize
    ) <= int(0.9 * _VMEM_BYTES)


def _band_geometry(
    hp: int, radius: int, band_rows: int
) -> tuple[int, int]:
    """(origin_rows, n_bands) for a padded level of height ``hp``: the
    ONE derivation of the band count, shared by :func:`band_plan` and
    the kernel-side geometry in :func:`_banded_lookup_one_level` so the
    planned count and the DMA/chunk-table layout can never drift.
    Clamped window origins span [0, hp - (K+1)] (the ``lim`` clip), so
    ``origin_rows = hp - K`` rows need band coverage."""
    origin_rows = hp - (2 * radius + 1)
    return origin_rows, max(1, -(-origin_rows // band_rows))


def _band_halo(radius: int) -> int:
    # Rows a band's slab extends past its last origin row: a window
    # origin on the band's final row reads K+1 rows, so K+1 is the hard
    # floor; K+2 keeps one spare row of the zero margin in-slab so a
    # clamped fully-OOB window stays entirely inside zeros even at the
    # band seam (mirrors the K+2 pad of _padded_hw).
    return 2 * radius + 3


def _banded_vmem_bytes(
    h: int,
    w: int,
    channels: int,
    radius: int,
    band_rows: int,
    query_block: int | None = None,
    itemsize: int = 4,
) -> int:
    """Bytes of VMEM the BANDED kernel needs for one (h, w) level at
    ``band_rows`` origin rows per band: the single-buffered band slab
    (``band_rows + K + 2`` padded rows — the level itself stays in HBM
    and the slab is DMA'd, so no pipeline double buffer) + the same
    double-buffered query blocks as the resident kernel, all at
    ``itemsize`` (the policy's corr dtype — bf16 halves
    every term, exactly the threshold doubling the resident tier
    already has; tests/test_precision.py pins the ratio for this budget
    too)."""
    if query_block is None:
        query_block = effective_query_block()
    _, wp, _ = _padded_hw(h, w, radius)
    wpa = _alloc_width(wp, radius, itemsize)
    slab = (band_rows + _band_halo(radius)) * wpa * channels
    return itemsize * slab + _block_bytes(
        channels, radius, query_block, itemsize
    )


def band_plan(
    h: int,
    w: int,
    channels: int,
    radius: int = 4,
    dtype=None,
    query_block: int | None = None,
) -> tuple[int, int] | None:
    """Band geometry for a level too large for the resident kernel:
    ``(band_rows, n_bands)``, or ``None`` when not even a 1-row band
    fits the budget (the level then falls back to XLA onthefly).

    ``band_rows`` is the largest count whose banded budget
    (:func:`_banded_vmem_bytes`) fits 0.9x VMEM at ``dtype``'s element
    size, rounded down to a multiple of 8 when >= 8 (sublane-friendly
    DMA rows); ``RAFT_NCUP_CORR_BAND_ROWS`` overrides it unconditionally
    (the autotuner's sweep knob — an expert override is trusted, the
    budget check is for the AUTO choice). ``n_bands`` partitions the
    clamped window-origin rows of the PADDED level."""
    if query_block is None:
        query_block = effective_query_block()
    itemsize = 4 if dtype is None else int(jnp.dtype(dtype).itemsize)
    hp, _, _ = _padded_hw(h, w, radius)
    origin_rows, _ = _band_geometry(hp, radius, 1)
    override = band_rows_override()
    if override is not None:
        band_rows = max(1, min(override, origin_rows))
    else:
        budget = int(0.9 * _VMEM_BYTES)
        fixed = _banded_vmem_bytes(
            h, w, channels, radius, 0, query_block, itemsize
        )
        if fixed > budget:
            return None  # blocks+halo alone blow the budget
        per_row = itemsize * channels * _alloc_width(
            w + 2 * (2 * radius + 3), radius, itemsize
        )
        band_rows = (budget - fixed) // per_row
        if band_rows < 1:
            return None
        band_rows = int(min(band_rows, origin_rows))
        if band_rows >= 8:
            band_rows -= band_rows % 8
    return band_rows, _band_geometry(hp, radius, band_rows)[1]


def _compiler_params():
    # The budget the dispatch plans against, stated to the compiler
    # instead of left to its default.
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES)


def _sublane_tile(itemsize: int) -> int:
    # Rows of one (sublane, lane) tile: 8 for 32-bit, 16 for bf16.
    return 8 * (4 // itemsize)


def _patch_cols(radius: int, itemsize: int) -> int:
    """Columns of the tile-aligned window that is sure to hold a query's
    K+1 patch columns whatever the residue of its start: K+1 plus a tile
    less one, rounded up to whole tiles."""
    a = _sublane_tile(itemsize)
    return -(-(2 * radius + 2 + a - 1) // a) * a


def _alloc_width(wp: int, radius: int, itemsize: int) -> int:
    """Columns a padded level (or band slab) is allocated with: its
    ``wp`` plus zero columns on the right, so that the aligned window of
    the right-most clamped origin (``wp - (K+1)``) is in-bounds, rounded
    up to whole sublane tiles."""
    a = _sublane_tile(itemsize)
    return -(-(wp + _patch_cols(radius, itemsize) - (2 * radius + 2)) // a) * a


def _fold_cols(radius: int, itemsize: int) -> int:
    """Columns the aligned window folds to before the reduce: the fewest
    whole sublane tiles that hold K+1 columns. The K+1 patch columns are
    consecutive, so they keep distinct positions modulo this width."""
    a = _sublane_tile(itemsize)
    return -(-(2 * radius + 2) // a) * a


def _group_windows(slab_ref, ibase_ref, f1_ref, frac_ref, base, radius: int):
    """The (G, K, K) float32 windows, natural (y, x) order, of the G
    queries at rows ``base .. base + G`` of the block's refs, read from
    ``slab_ref`` (rows, cols, C) at the origins ``ibase_ref`` holds: the
    one body of both kernel tiers, so they agree bit for bit.

    Reduce over the channels first, align afterwards (module
    docstring, "Kernel shape"). Rows are a leading, untiled dimension
    and take any dynamic start. Columns ride the sublane tile, where
    Mosaic accepts a dynamic start only if it is provably tile-aligned
    (the chip's compiler refused the direct ``pl.ds(ix, K+1)`` load), so
    each query loads the aligned window that holds its patch,
    ``_patch_cols`` wide, and the shift by the residue ``ix % tile`` is
    applied to the correlations, not to the C-channel data: it acts on
    the column axis and commutes with the sum over channels. Columns of
    the window outside the patch are in-bounds slab data
    (:func:`_alloc_width`): computed, and dropped by the final slice."""
    K = 2 * radius + 1
    K1 = K + 1
    G = _GROUP
    L = _QUERY_LANES
    assert K1 <= L, (radius, L)  # _level_tiers sends wider windows to XLA
    itemsize = jnp.dtype(slab_ref.dtype).itemsize
    a = _sublane_tile(itemsize)
    F = _fold_cols(radius, itemsize)
    # One tile of columns lies past the fold (K+1 is even, so it is
    # never 1 modulo the tile and _patch_cols rounds up past it).
    assert _patch_cols(radius, itemsize) == F + a
    f32 = jnp.float32

    f1g = f1_ref[pl.ds(base, G), :].astype(f32)  # (G, C)
    lane = jax.lax.broadcasted_iota(jnp.int32, (F, 128), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (a, 1), 0)
    packed = jnp.zeros((F, 128), f32)
    for g in range(G):
        ix = ibase_ref[base + g, 0]
        iy = ibase_ref[base + g, 1]
        ix0 = pl.multiple_of((ix // a) * a, a)
        res = ix - ix0
        f1q = f1g[g : g + 1, :]  # (1, C)
        # Two dynamic addresses for the window, the fold and the tile
        # past it; the rows are static offsets from them.
        rows = slab_ref[pl.ds(iy, K1), pl.ds(ix0, F), :]  # (K1, F, C)
        past = slab_ref[pl.ds(iy, K1), pl.ds(pl.multiple_of(ix0 + F, a), a), :]
        # This query's correlations, [column mod F, lane g * L + y].
        mine = jnp.zeros((F, 128), f32)
        for y in range(K1):
            row = rows[y].astype(f32)  # (F, C)
            # Fold: a column before the residue is not the patch's; its
            # place takes the column F further right, which may be.
            head = jnp.where(sub >= res, row[:a], past[y].astype(f32))
            row = head if F == a else jnp.concatenate([head, row[a:]], axis=0)
            corr = jnp.sum(row * f1q, axis=1, keepdims=True)  # (F, 1)
            mine = jnp.where(lane == g * L + y, corr, mine)
        # Align: the patch's first column to sublane 0. The queries'
        # lanes are disjoint, so the sum only merges them.
        packed = packed + pltpu.roll(mine, (F - res) % F, axis=0)

    # Per-lane blend weights: lane l belongs to query l // L.
    fr = frac_ref[pl.ds(base, G), :]  # (G, 2)
    ql = jax.lax.broadcasted_iota(jnp.int32, (G, 128), 1)
    qs = jax.lax.broadcasted_iota(jnp.int32, (G, 128), 0)
    own = (ql >= qs * L) & (ql < (qs + 1) * L)
    fx = jnp.sum(jnp.where(own, fr[:, 0:1], 0.0), axis=0, keepdims=True)
    fy = jnp.sum(jnp.where(own, fr[:, 1:2], 0.0), axis=0, keepdims=True)
    nx = pltpu.roll(packed, F - 1, axis=0)  # column x + 1
    ny = pltpu.roll(packed, 127, axis=1)  # row y + 1
    nxy = pltpu.roll(nx, 127, axis=1)
    win = (
        (1 - fy) * (1 - fx) * packed
        + (1 - fy) * fx * nx
        + fy * (1 - fx) * ny
        + fy * fx * nxy
    )  # (F, 128): [x, g * L + y]
    return win.T.reshape(G, L, F)[:, :K, :K]


def _lookup_kernel(ibase_ref, f1_ref, frac_ref, f2_ref, out_ref, *, radius):
    """One (batch, query-block) program, vectorized over groups of _GROUP.

    ibase_ref:   (Q, 2) int32, SMEM — clamped window origins (x, y) in the
                 padded level.
    f1_ref:      (Q, C) compute dtype — query features, pre-scaled by
                 1/sqrt(C).
    frac_ref:    (Q, 2) float32 — sub-pixel offsets (fx, fy).
    f2_ref:      (Hp, Wp, C) compute dtype — zero-padded fmap2 level
                 (bf16 under the bf16 policies: the resident slab is the
                 VMEM term, so narrow STORAGE is the dispatch-threshold
                 win; the reduce upcasts, so ACCUMULATION is f32).
    out_ref:     (Q, K, K) float32 — window values in natural (y, x) order;
                 the caller transposes to the reference's x-major tap order
                 (core/corr.py:31-37).
    """
    G = _GROUP

    def body(i, _):
        base = pl.multiple_of(i * G, G)
        out_ref[pl.ds(base, G)] = _group_windows(
            f2_ref, ibase_ref, f1_ref, frac_ref, base, radius
        )
        return 0

    jax.lax.fori_loop(0, out_ref.shape[0] // G, body, 0)


def _pad_level(
    f2l: jax.Array, radius: int, band_rows: int | None = None
) -> jax.Array:
    """The zero-padded (B, Hl, Wl, C) pooled level as a kernel reads it:
    K + 2 zeros per side (:func:`_padded_hw`), zero columns on the right
    up to :func:`_alloc_width`, and for the banded tier (``band_rows``
    given) zero rows below so that every band's slab (``band_rows`` +
    halo rows from its first origin row) is in-bounds — zeros, i.e. the
    margin the clamped-origin semantics already rely on. The features
    and ``radius`` fix it: once per pair, outside the refinement loop
    (:func:`prepare_lookup`)."""
    _, Hl, Wl, _ = f2l.shape
    Hp, Wp, pad = _padded_hw(Hl, Wl, radius)
    Wpa = _alloc_width(Wp, radius, jnp.dtype(f2l.dtype).itemsize)
    extra = 0
    if band_rows is not None:
        _, n_bands = _band_geometry(Hp, radius, band_rows)
        extra = n_bands * band_rows + _band_halo(radius) - Hp
    return jnp.pad(
        f2l, ((0, 0), (pad, pad + extra), (pad, pad + Wpa - Wp), (0, 0))
    )


def _lookup_one_level(
    f1: jax.Array,  # (B, N, C) pre-scaled query features, N = H*W
    f2p: jax.Array,  # (B, Hp, Wpa, C) level padded by _pad_level
    level_hw: tuple[int, int],  # (Hl, Wl) of the pooled level
    coords: jax.Array,  # (B, N, 2)
    radius: int,
    level: int,
    interpret: bool = False,
    query_block: int = _QUERY_BLOCK,
) -> jax.Array:
    B, N, C = f1.shape
    Hl, Wl = level_hw
    # Feature operands keep their (policy-chosen) dtype end to end: the
    # VMEM-resident slab and the f1 blocks are what the budget counts.
    fdt = f1.dtype
    K = 2 * radius + 1
    Hp, Wp, pad = _padded_hw(Hl, Wl, radius)
    Wpa = f2p.shape[2]

    # Window origin + sub-pixel offset per query, computed on the XLA side
    # so the kernel's SMEM operand is plain int32 indices.
    cl = coords.astype(jnp.float32) / (2.0**level)
    c0 = jnp.floor(cl)
    frac = cl - c0  # (B, N, 2): (fx, fy)
    lim = jnp.asarray([Wp - (K + 1), Hp - (K + 1)], jnp.int32)
    ibase = jnp.clip(c0.astype(jnp.int32) - radius + pad, 0, lim)

    qblk = min(query_block, max(_GROUP, (N + _GROUP - 1) // _GROUP * _GROUP))
    qblk = max(qblk - qblk % _GROUP, _GROUP)
    n_pad = (-N) % qblk
    if n_pad:
        f1 = jnp.pad(f1, ((0, 0), (0, n_pad), (0, 0)))
        frac = jnp.pad(frac, ((0, 0), (0, n_pad), (0, 0)))
        ibase = jnp.pad(ibase, ((0, 0), (0, n_pad), (0, 0)))
    n_blocks = (N + n_pad) // qblk

    # Integer window origins live in SMEM (the home for indices driving
    # dynamic slices); interpret mode keeps the default space since the
    # CPU interpreter has no SMEM emulation for blocked operands.
    ibase_spec = pl.BlockSpec(
        (None, qblk, 2),
        lambda b, i: (b, i, 0),
        **({} if interpret else {"memory_space": pltpu.SMEM}),
    )

    out = pl.pallas_call(
        functools.partial(_lookup_kernel, radius=radius),
        grid=(B, n_blocks),
        in_specs=[
            ibase_spec,
            pl.BlockSpec((None, qblk, C), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, qblk, 2), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Hp, Wpa, C), lambda b, i: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, qblk, K, K), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N + n_pad, K, K), jnp.float32),
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=f"corr_resident_l{level}",
    )(
        ibase,
        f1.astype(fdt),
        frac.astype(jnp.float32),
        f2p.astype(fdt),
    )
    return _x_major_taps(out)[:, :N]


def _x_major_taps(out: jax.Array) -> jax.Array:
    """A kernel's (B, Nq, K_y, K_x) windows as (B, Nq, K*K) rows of
    x-major taps (the reference's order, core/corr.py:31-37). FIRST, before
    any row is dropped or permuted: the kernel's (9, 9) windows sit in
    (16, 128) tiles, 8 KB a query, 1.07 GB a level for a batch of four
    1080p pairs, and XLA ran a slice or a gather over that form as a copy
    of all of it (6.5 and 6.9 ms an iteration each on a v5e, PR 32); over
    the 81-tap rows the same slice or gather moves a sixteenth."""
    B, Nq, K, _ = out.shape
    return out.transpose(0, 1, 3, 2).reshape(B, Nq, K * K)


def _banded_lookup_kernel(
    tbl_ref, ibase_ref, f1_ref, frac_ref, f2_ref, out_ref,
    slab_ref, sem, *, radius, qblk, band_rows,
):
    """One (batch, chunk) program of the banded tier.

    tbl_ref:     (B, n_chunks, 5) int32, SMEM (scalar prefetch) — per
                 chunk: band id, query-block id, [lo, hi) sorted-query
                 range, fresh-band flag (1 = DMA a new band slab).
    ibase_ref:   (Q, 2) int32, SMEM — clamped window origins per SORTED
                 query: (x in the padded level, y LOCAL to the band).
    f1_ref:      (Q, C) compute dtype — sorted query features.
    frac_ref:    (Q, 2) float32 — sorted sub-pixel offsets (fx, fy).
    f2_ref:      (B, Hb, Wp, C) compute dtype, HBM (memory_space=ANY) —
                 the whole zero-padded level; never resident.
    out_ref:     (Q, K, K) float32 — window values in SORTED query
                 order, natural (y, x); revisited consecutively by the
                 chunks of one query block (accumulation pattern).
    slab_ref:    (band_rows + K + 2, Wp, C) VMEM scratch — the band
                 slab, DMA'd from HBM on a fresh-band chunk. Single
                 buffered: this is what the banded budget counts.
    sem:         DMA completion semaphore.
    """
    G = _GROUP
    b = pl.program_id(0)
    j = pl.program_id(1)
    band = tbl_ref[b, j, 0]
    lo = tbl_ref[b, j, 2]
    hi = tbl_ref[b, j, 3]
    base_q = tbl_ref[b, j, 1] * qblk

    @pl.when(tbl_ref[b, j, 4] == 1)
    def _copy_band():
        # Synchronous band-slab DMA: consecutive chunks of one band skip
        # it (fresh flag 0), so the level streams from HBM once per band
        # plus halo overlap. No double buffer — the whole point of the
        # banded budget (see _banded_vmem_bytes).
        cp = pltpu.make_async_copy(
            f2_ref.at[b, pl.ds(band * band_rows, slab_ref.shape[0])],
            slab_ref,
            sem,
        )
        cp.start()
        cp.wait()

    @pl.when(lo == base_q)
    def _init_block():
        # First chunk of this query block zero-inits the out block; the
        # block stays VMEM-resident across its (consecutive) chunks.
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, _):
        gbase = pl.multiple_of(i * G, G)
        q0 = base_q + gbase

        @pl.when((q0 + G > lo) & (q0 < hi))
        def _group():
            # Masked group: the resident kernel's windows, read from the
            # band slab at band-local row origins; lanes outside
            # [lo, hi) (a boundary group's neighbours from the adjacent
            # band) are computed against this band's slab (memory-safe
            # via the band-local clamp) and masked out of the
            # accumulate, so the neighbouring chunk supplies them.
            win = _group_windows(
                slab_ref, ibase_ref, f1_ref, frac_ref, gbase, radius
            )
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (G, 1, 1), 0)
            mask = (qpos >= lo) & (qpos < hi)
            cur = out_ref[pl.ds(gbase, G)]
            out_ref[pl.ds(gbase, G)] = cur + jnp.where(mask, win, 0.0)
        return 0

    jax.lax.fori_loop(0, out_ref.shape[0] // G, body, 0)


def _banded_lookup_one_level(
    f1: jax.Array,  # (B, N, C) pre-scaled query features, N = H*W
    f2p: jax.Array,  # (B, Hb, Wpa, C) level padded by _pad_level(band_rows)
    level_hw: tuple[int, int],  # (Hl, Wl) of the pooled level
    coords: jax.Array,  # (B, N, 2)
    radius: int,
    level: int,
    band_rows: int,
    interpret: bool = False,
    query_block: int | None = None,
) -> jax.Array:
    """Banded variant of :func:`_lookup_one_level` for levels whose
    padded slab exceeds the resident VMEM budget (module docstring,
    "Banded tier"). Bitwise-equal to the resident kernel: identical
    per-query math, only regrouped — the parity is pinned by
    tests/test_corr_pallas.py."""
    B, N, C = f1.shape
    Hl, Wl = level_hw
    fdt = f1.dtype
    K = 2 * radius + 1
    K1 = K + 1
    halo = _band_halo(radius)
    Hp, Wp, pad = _padded_hw(Hl, Wl, radius)
    _, n_bands = _band_geometry(Hp, radius, band_rows)
    Wpa = f2p.shape[2]
    f2p = f2p.astype(fdt)

    # Everything XLA does per iteration to feed the kernel — band
    # assignment, the stable argsort, the row permutations and the chunk
    # table — under one scope, so a capture tells it from the kernel.
    with jax.named_scope(_SORT_SCOPE):
        cl = coords.astype(jnp.float32) / (2.0**level)
        c0 = jnp.floor(cl)
        frac = cl - c0  # (B, N, 2): (fx, fy)
        lim = jnp.asarray([Wp - K1, Hp - K1], jnp.int32)
        ib = jnp.clip(c0.astype(jnp.int32) - radius + pad, 0, lim)
        band_id = ib[..., 1] // band_rows  # (B, N)
        # Window origins as the kernel reads them: x in the padded level,
        # y LOCAL to the query's own band slab.
        ibase = jnp.stack(
            [ib[..., 0], ib[..., 1] - band_id * band_rows], axis=-1
        )

        # Stable argsort-by-band: queries of one band become contiguous (and
        # keep raster order within it); the inverse permutation restores the
        # caller's order after the kernel.
        order = jnp.argsort(band_id, axis=1, stable=True)

        def take(x):
            return jnp.take_along_axis(x, order[..., None], axis=1)

        f1_s, frac_s, ibase_s = take(f1), take(frac), take(ibase)
        band_s = jnp.take_along_axis(band_id, order, axis=1)

        qblk = query_block or effective_query_block()
        qblk = min(qblk, max(_GROUP, (N + _GROUP - 1) // _GROUP * _GROUP))
        qblk = max(qblk - qblk % _GROUP, _GROUP)
        n_pad = (-N) % qblk
        if n_pad:
            f1_s = jnp.pad(f1_s, ((0, 0), (0, n_pad), (0, 0)))
            frac_s = jnp.pad(frac_s, ((0, 0), (0, n_pad), (0, 0)))
            ibase_s = jnp.pad(ibase_s, ((0, 0), (0, n_pad), (0, 0)))
            # Padding queries ride the last band (edge mode) so they extend
            # its final chunk instead of minting a fresh one; their ibase is
            # (0, 0) — in-slab reads, results dropped by the un-sort (the
            # inverse permutation has N rows).
            band_s = jnp.pad(band_s, ((0, 0), (0, n_pad)), mode="edge")
        Nq = N + n_pad
        n_blocks = Nq // qblk

        # Chunk table: the sorted query array cut at every query-block start
        # and band change — the (band x query_block) grid with empty cells
        # compressed out. At most n_blocks + n_bands - 1 segments; unused
        # slots become dummy chunks (lo == hi == Nq, clamped to the last
        # block and band, fresh=0) that fetch nothing new and mask all work.
        n_chunks = n_blocks + n_bands - 1
        pos = jnp.arange(Nq, dtype=jnp.int32)
        newband = jnp.concatenate(
            [jnp.ones((B, 1), bool), band_s[:, 1:] != band_s[:, :-1]], axis=1
        )
        is_start = newband | ((pos % qblk) == 0)[None, :]
        starts = jnp.sort(
            jnp.where(is_start, pos[None], Nq).astype(jnp.int32), axis=1
        )[:, :n_chunks]
        ends = jnp.minimum(
            jnp.concatenate(
                [starts[:, 1:], jnp.full((B, 1), Nq, jnp.int32)], axis=1
            ),
            Nq,
        )
        blk = jnp.minimum(starts // qblk, n_blocks - 1)
        bnd = jnp.take_along_axis(
            band_s, jnp.minimum(starts, Nq - 1), axis=1
        ).astype(jnp.int32)
        fresh = jnp.concatenate(
            [
                jnp.ones((B, 1), jnp.int32),
                (bnd[:, 1:] != bnd[:, :-1]).astype(jnp.int32),
            ],
            axis=1,
        )
        fresh = jnp.where(starts < Nq, fresh, 0)  # dummies never DMA
        tbl = jnp.stack([bnd, blk, starts, ends, fresh], axis=-1)

    ibase_spec = pl.BlockSpec(
        (None, qblk, 2),
        lambda b, j, t: (b, t[b, j, 1], 0),
        **({} if interpret else {"memory_space": pltpu.SMEM}),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_chunks),
        in_specs=[
            ibase_spec,
            pl.BlockSpec(
                (None, qblk, C), lambda b, j, t: (b, t[b, j, 1], 0)
            ),
            pl.BlockSpec(
                (None, qblk, 2), lambda b, j, t: (b, t[b, j, 1], 0)
            ),
            pl.BlockSpec(memory_space=pl.ANY),  # level stays in HBM
        ],
        out_specs=pl.BlockSpec(
            (None, qblk, K, K), lambda b, j, t: (b, t[b, j, 1], 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((band_rows + halo, Wpa, C), fdt),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _banded_lookup_kernel,
            radius=radius,
            qblk=qblk,
            band_rows=band_rows,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Nq, K, K), jnp.float32),
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=f"corr_banded_l{level}",
    )(
        tbl,
        ibase_s,
        f1_s.astype(fdt),
        frac_s.astype(jnp.float32),
        f2p,
    )
    taps = _x_major_taps(out)
    with jax.named_scope(_SORT_SCOPE):  # back to the caller's order
        inv = jnp.argsort(order, axis=1)  # (B, N): the padding rows drop out
        return jnp.take_along_axis(taps, inv[..., None], axis=1)


def _level_tiers(
    H: int, W: int, C: int, radius: int, num_levels: int, dtype, qblk: int
) -> list[tuple[str, tuple[int, int], int | None]]:
    """The static THREE-TIER dispatch of every pyramid level of an (H, W)
    1/8-resolution map at ``dtype``'s element size, from shapes alone:
    ``(tier, (Hl, Wl), band_rows)`` with tier ``kernel`` (the padded slab
    fits VMEM: resident), ``banded`` (a fitting :func:`band_plan`) or
    ``fallback`` (XLA onthefly). :func:`prepare_lookup` and the lookup
    both call it, so the padded levels made once per pair are the ones
    each iteration's kernels expect."""
    tiers = []
    # The group body packs a query's K+1 window rows into its lanes
    # (:func:`_group_windows`): a wider window is XLA's.
    packs = 2 * radius + 2 <= _QUERY_LANES
    for lvl in range(num_levels):
        Hl, Wl = H >> lvl, W >> lvl  # avg_pool2 floors
        if not packs:
            tiers.append(("fallback", (Hl, Wl), None))
        elif fits_vmem(Hl, Wl, C, radius, dtype=dtype):
            tiers.append(("kernel", (Hl, Wl), None))
        elif plan := band_plan(
            Hl, Wl, C, radius, dtype=dtype, query_block=qblk
        ):
            tiers.append(("banded", (Hl, Wl), plan[0]))
        else:
            tiers.append(("fallback", (Hl, Wl), None))
    return tiers


def prepare_lookup(
    fmap1: jax.Array,
    fmap2: jax.Array,
    radius: int,
    num_levels: int = 4,
    dtype=None,
) -> tuple:
    """What the lookup makes of the feature maps alone, ONCE per pair:
    ``(f1, levels)``, the pre-scaled (B, H*W, C) query features and per
    pyramid level the pooled fmap2 level zero-padded as its kernel tier
    reads it (``None`` for a level that falls back to XLA). A caller
    that looks up many times over the same features (the refinement
    loop) makes this before the loop and hands it to
    :func:`corr_lookup_pallas` as ``prepared``, so no iteration pools or
    pads; without it every call prepares for itself."""
    from raft_ncup_tpu.ops.corr import _pool_fmap_pyramid

    B, H, W, C = fmap1.shape
    dtype = jnp.dtype(dtype) if dtype is not None else jnp.float32
    tiers = _level_tiers(
        H, W, C, radius, num_levels, dtype, effective_query_block()
    )
    with jax.named_scope(_PAD_SCOPE):
        f1 = (fmap1.reshape(B, H * W, C) * (1.0 / math.sqrt(C))).astype(dtype)
        levels = tuple(
            None if tier == "fallback" else _pad_level(f2l, radius, band_rows)
            for f2l, (tier, _, band_rows) in zip(
                _pool_fmap_pyramid(fmap2.astype(dtype), num_levels), tiers
            )
        )
    return f1, levels


def _forward(
    fmap1: jax.Array,
    fmap2: jax.Array,
    coords: jax.Array,
    radius: int,
    num_levels: int,
    interpret: bool = False,
    dtype=None,
    prepared: tuple | None = None,
) -> jax.Array:
    """Volume-free fused lookup over all pyramid levels, with PER-LEVEL
    THREE-TIER dispatch at ``dtype``'s element size: levels whose
    padded slab fits VMEM take the resident kernel, levels too large
    for residency but with a fitting :func:`band_plan` take the banded
    kernel, and only the remainder takes the equivalent XLA on-the-fly
    path (at 1080p f32 levels 0-1 are banded, 2-3 resident; at 4K every
    level lands on a kernel tier — tests/test_pallas_lowering.py pins
    the exact counts, tests/test_precision.py the bf16 threshold
    ratios)."""
    from raft_ncup_tpu.ops.corr import corr_lookup_onthefly

    B, H, W, C = fmap1.shape
    dtype = jnp.dtype(dtype) if dtype is not None else jnp.float32
    if prepared is None:
        prepared = prepare_lookup(fmap1, fmap2, radius, num_levels, dtype)
    f1, levels = prepared
    cflat = coords.astype(jnp.float32).reshape(B, H * W, 2)

    qblk = effective_query_block()
    K2 = (2 * radius + 1) ** 2
    outs: dict[int, jax.Array] = {}
    fallback = []
    _count("levels_total", num_levels)
    tiers = _level_tiers(H, W, C, radius, num_levels, dtype, qblk)
    for lvl, (tier, level_hw, band_rows) in enumerate(tiers):
        _count(tier)
        if tier == "kernel":
            outs[lvl] = _lookup_one_level(
                f1, levels[lvl], level_hw, cflat, radius, lvl,
                interpret=interpret, query_block=qblk,
            )
        elif tier == "banded":
            outs[lvl] = _banded_lookup_one_level(
                f1, levels[lvl], level_hw, cflat, radius, lvl,
                band_rows=band_rows, interpret=interpret, query_block=qblk,
            )
        else:
            fallback.append(lvl)
    if fallback:
        if len(fallback) == num_levels:
            # Every level rejected by BOTH kernel tiers (resident
            # fits_vmem AND band_plan): corr_impl='pallas' would be pure
            # XLA onthefly under the kernel's name. On the chip that is
            # an error; elsewhere (interpret-mode tests) a warning.
            from raft_ncup_tpu.utils.runtime import is_tpu_backend

            msg = (
                f"all {num_levels} corr pyramid levels exceed the VMEM "
                "budget; corr_impl='pallas' would run the XLA onthefly "
                "fallback for every level"
            )
            if is_tpu_backend():
                raise RuntimeError(msg + " — select corr_impl='onthefly'")
            import warnings

            warnings.warn(msg, stacklevel=2)
        fb = corr_lookup_onthefly(
            fmap1, fmap2, coords, radius, num_levels, levels=tuple(fallback),
            dtype=dtype,
        ).reshape(B, H * W, len(fallback) * K2)
        for j, lvl in enumerate(fallback):
            outs[lvl] = fb[..., j * K2 : (j + 1) * K2]

    return jnp.concatenate(
        [outs[lvl] for lvl in range(num_levels)], axis=-1
    ).reshape(B, H, W, num_levels * K2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def corr_lookup_pallas(
    fmap1: jax.Array,
    fmap2: jax.Array,
    coords: jax.Array,
    radius: int,
    num_levels: int = 4,
    interpret: bool = False,
    dtype=None,
    prepared: tuple | None = None,
) -> jax.Array:
    """Fused correlation lookup: (B,H,W,C) x2 + (B,H,W,2) ->
    (B, H, W, L*(2r+1)^2) float32. Equivalent to the XLA paths in
    ``raft_ncup_tpu.ops.corr`` up to float associativity; never
    materializes the correlation volume. ``dtype`` (static; default
    f32) is the feature/slab dtype the per-level THREE-TIER dispatch
    (resident kernel -> banded kernel -> XLA onthefly) budgets with —
    the precision policy's ``corr_jnp``. ``prepared``:
    :func:`prepare_lookup`'s result for the same features, radius,
    levels and dtype, made once by a caller that looks up in a loop; a
    cache of the features, not an input of its own (its cotangent is
    zero). The backward always differentiates the f32 XLA path:
    gradients stay full precision regardless of the forward's storage
    dtype (f32 master weights)."""
    return _forward(
        fmap1, fmap2, coords, radius, num_levels, interpret, dtype, prepared
    )


def _fwd(fmap1, fmap2, coords, radius, num_levels, interpret, dtype, prepared):
    out = _forward(
        fmap1, fmap2, coords, radius, num_levels, interpret, dtype, prepared
    )
    return out, (fmap1, fmap2, coords, prepared)


def _bwd(radius, num_levels, interpret, dtype, res, g):
    from raft_ncup_tpu.ops.corr import corr_lookup_onthefly

    fmap1, fmap2, coords, prepared = res
    # Backward through the mathematically equivalent XLA implementation —
    # autodiff of the gather path gives exact gradients for the same
    # function value.
    _, vjp = jax.vjp(
        lambda a, b, c: corr_lookup_onthefly(a, b, c, radius, num_levels),
        fmap1,
        fmap2,
        coords,
    )
    return (*vjp(g), jax.tree.map(jnp.zeros_like, prepared))


corr_lookup_pallas.defvjp(_fwd, _bwd)
