"""Device mesh construction.

The parallelism model (TPU-native replacement for the reference's
single-process ``nn.DataParallel`` over 2 GPUs, reference: train.py:169-175):

- axis ``data``: batch-sharded data parallelism. Gradients are averaged by
  XLA-inserted psums over ICI — the jit partitioner sees replicated params
  and a sharded batch and does the rest.
- axis ``spatial``: the image-height dimension is sharded — the convnet
  analogue of sequence/context parallelism. XLA inserts halo exchanges
  for spatially-sharded convolutions automatically. This is what lets
  1080p 32-iteration inference (whose correlation volume would otherwise
  be several GB) scale across chips.

Multi-host: ``jax.distributed.initialize`` + the same mesh spanning all
processes; each host feeds its local shard of the batch
(``jax.make_array_from_process_local_data``).
"""

from __future__ import annotations

import warnings
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    data: Optional[int] = None,
    spatial: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (data, spatial) mesh. ``data=None`` uses all remaining
    devices after spatial partitioning.

    An explicit ``data`` x ``spatial`` smaller than the device set warns
    loudly: the stripped devices sit idle for the whole program, which
    is a legitimate ops choice (e.g. ``--spatial_parallel 2`` on an
    8-chip host while debugging) but must never happen silently — a
    mis-sized mesh that quietly drops 6 of 8 chips looks exactly like a
    4x perf regression.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if data is None:
        if n % spatial:
            raise ValueError(
                f"{n} devices not divisible by spatial={spatial}"
            )
        data = n // spatial
    use = data * spatial
    if use > n:
        raise ValueError(
            f"mesh {data}x{spatial} needs {use} devices, have {n}"
        )
    if use < n:
        warnings.warn(
            f"mesh {data}x{spatial} uses only {use} of {n} visible "
            f"devices; {n - use} device(s) will sit idle. Pass data=None "
            "to span all devices, or restrict `devices=` explicitly if "
            "the subset is intentional.",
            stacklevel=2,
        )
    arr = np.asarray(devices[:use]).reshape(data, spatial)
    return Mesh(arr, ("data", "spatial"))


def resolve_config_mesh(mesh, cfg_mesh) -> tuple:
    """The serving/streaming mesh-resolution rule, in one place: an
    explicit ``mesh`` wins, else a config's ``(data, spatial)`` sizes
    build one, else unsharded. Returns ``(mesh_or_None, pad_divisor)``
    where the divisor is 8*spatial — every image padded for this mesh
    must round to it so the 1/8-res feature height divides the spatial
    axis (evaluation._pad_divisor's rule)."""
    if mesh is None and cfg_mesh is not None:
        data, spatial = cfg_mesh
        mesh = make_mesh(data=int(data), spatial=int(spatial))
    spatial = int(mesh.shape.get("spatial", 1)) if mesh is not None else 1
    return mesh, 8 * spatial


def mesh_fingerprint(mesh: Optional[Mesh]) -> str:
    """Stable, hashable identity of a mesh configuration — part of every
    compiled-executable cache key on the inference/serving/streaming
    path (inference/pipeline.ShapeCachedForward) and of the bench rows'
    sharding provenance. Two programs compiled for different meshes (or
    sharded vs unsharded) must never collide in a cache, and a recorded
    number must say which mesh produced it."""
    if mesh is None:
        return "nomesh"
    axes = ",".join(f"{k}={v}" for k, v in mesh.shape.items())
    platform = next(iter(mesh.devices.flat)).platform
    return f"mesh({axes}:{platform})"


_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def collective_stats(hlo_text: str) -> dict:
    """Sharding fingerprint of a compiled executable: how many
    cross-device collective ops the partitioner inserted and the total
    bytes they produce, parsed from the optimized HLO text
    (``compiled.as_text()``), plus the same pair broken out per op kind
    under ``by_op`` — ``{"all-gather": {"count": n, "bytes": b}, ...}``
    with every kind in ``_COLLECTIVE_OPS`` present (zeros included, so
    consumers index without guards). The breakout is what lets halo
    exchanges (``collective-permute``) be told from the fmap2
    all-gathers and the gradient all-reduces of one program.

    An unsharded program has zero of everything; a spatially-sharded
    forward shows the halo exchanges and the replicated-fmap2
    all-gathers the mesh costs. The byte count is approximate (result
    shapes only, async start/done pairs counted once via the ``-start``
    form) — it is a fingerprint for bench rows
    (``highres_collective_bytes``), not an interconnect-traffic model.
    """
    import re

    shape_re = re.compile(r"(\w+)\[([0-9,]*)\]")
    by_op = {op: {"count": 0, "bytes": 0} for op in _COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        # `%x = TYPE op-name(...)`: match the op between the result type
        # and its operand list; skip `-done` halves of async pairs.
        hit = None
        hit_op = None
        for op in _COLLECTIVE_OPS:
            for form in (f" {op}(", f" {op}-start("):
                idx = line.find(form)
                if idx != -1:
                    hit = idx
                    hit_op = op
                    break
            if hit is not None:
                break
        if hit is None or "=" not in line[:hit]:
            continue
        by_op[hit_op]["count"] += 1
        result = line[line.index("=") + 1: hit]
        for dtype, dims in shape_re.findall(result):
            nbytes = _DTYPE_BYTES.get(dtype)
            if nbytes is None:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            by_op[hit_op]["bytes"] += n * nbytes
    return {
        "collectives": sum(v["count"] for v in by_op.values()),
        "collective_bytes": sum(v["bytes"] for v in by_op.values()),
        "by_op": by_op,
    }


def batch_sharding(mesh: Mesh) -> dict:
    """Shardings for a training batch dict: batch over 'data', image height
    over 'spatial'."""
    img = NamedSharding(mesh, P("data", "spatial", None, None))
    return {
        "image1": img,
        "image2": img,
        "flow": img,
        "valid": NamedSharding(mesh, P("data", "spatial", None)),
    }


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
