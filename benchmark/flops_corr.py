"""Operations and bytes one correlation lookup needs, from shapes alone,
whatever implements it: the yardstick of ``corr_kernel_roofline_pct``.

Per pair and refinement iteration, at 1/8 resolution (h8, w8), with
``levels`` pyramid levels, window radius ``r`` and ``channels`` features:

- operations: every query's (2r+1)^2 window with one sub-pixel offset is a
  2x2 blend of a (2r+2)^2 patch of integer-aligned correlations, each a
  ``channels``-long dot product (a multiply-add is 2):
  ``2 * h8*w8 * (2r+2)^2 * channels * levels``. The blend itself is ignored
  (under 2%).
- bytes, each array once, float32: ``fmap1`` (the queries' features), the
  pooled ``fmap2`` pyramid (level l is h8 // 2^l by w8 // 2^l), the
  coordinates, and the ``levels * (2r+1)^2``-channel output.

The roofline time is the larger of operations over the chip's peak FLOP/s
and bytes over its peak bytes/s (``benchmark/peaks.json``, as published; the
lookup multiplies in float32 on the vector units, which the published bf16
matrix peak flatters: the share read against it is a floor on how far the
kernel is from the chip, never an excuse).

``model`` is the ``model`` section of a configuration file.
"""

from __future__ import annotations

FNET_DIM = 256  # the feature encoder's width (``widths.fnet_dim``), as flops.py has it


def _geometry(model: dict) -> tuple[int, int]:
    return int(model.get("corr_levels", 4)), int(model.get("corr_radius", 4))


def lookup_ops(model: dict, h8: int, w8: int, channels: int = FNET_DIM) -> float:
    """Operations of one lookup of one pair."""
    levels, r = _geometry(model)
    return 2.0 * h8 * w8 * (2 * r + 2) ** 2 * channels * levels


def lookup_bytes(model: dict, h8: int, w8: int, channels: int = FNET_DIM) -> float:
    """Bytes one lookup of one pair has to move, each array once."""
    levels, r = _geometry(model)
    queries = h8 * w8
    pyramid = sum((h8 >> lvl) * (w8 >> lvl) for lvl in range(levels)) * channels
    out = queries * levels * (2 * r + 1) ** 2
    return 4.0 * (queries * channels + pyramid + queries * 2 + out)


def lookup_roofline_s(model: dict, h8: int, w8: int, iters: int, peaks: dict) -> dict:
    """The least time the chip could take for one pair's ``iters`` lookups,
    and which of the two bounds it."""
    by_ops = lookup_ops(model, h8, w8) / peaks["flops_per_s"]
    by_bytes = lookup_bytes(model, h8, w8) / peaks["bytes_per_s"]
    return {
        "seconds": iters * max(by_ops, by_bytes),
        "bound": "memory" if by_bytes >= by_ops else "compute",
    }
