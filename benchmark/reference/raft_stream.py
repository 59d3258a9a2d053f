"""The plain reference of the Sintel warm-start protocol: RAFT / RAFT-NCUP's
test-mode forward with ``flow_init``, upstream's ``forward_interpolate``, and
one video session walked one frame pair at a time.

Upstream: github.com/abdo-eldesokey/RAFT-NCUP ``evaluate.py:22-57``
(``create_sintel_submission(..., warm_start=True)``, inherited from
princeton-vl/RAFT), ``core/raft.py:114-117`` (``coords1 = coords1 +
flow_init``) and ``core/utils/utils.py:28-56`` (``forward_interpolate``).
Float32, every product at ``highest``, batch 1, Python loops; no slot table,
no batching, no code of the engine. The blocks of the forward (encoders,
pyramid, lookup, update block, upsamplers) are ``benchmark/reference/raft.py``'s.

Departures from upstream, all of them:

- ``forward_interpolate`` there is scipy's ``griddata(..., method="nearest")``
  on the float landing points (a k-d tree, ties broken by the tree's order).
  Here: brute force, the squared distance from every grid point to every
  valid landing, and the FIRST index of the smallest (``argmin``). Equal
  except at exact distance ties, which a continuous flow field gives with
  probability zero. When no landing survives, zeros (upstream would raise;
  the program answers cold).
- Upstream pulls the low-resolution flow to the host between pairs and splats
  it there in float64 coordinates (numpy's ``meshgrid`` of integers plus a
  float32 flow); here the sum is float32, as the program's is on the device.
- Sessions: the program batches frames of different sessions; the reference
  never does. One session, one pair at a time, ``flow_prev = None`` first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.raft import Reference, _coords, pad_sintel

SPLAT_BLOCK = 1024  # grid points whose distances are held at once


def forward_interpolate(flow: jax.Array) -> jax.Array:
    """(H, W, 2) flow of pair t -> (H, W, 2) initial flow for pair t+1."""
    flow = jnp.asarray(flow, jnp.float32)
    ht, wd = flow.shape[:2]
    grid = _coords(1, ht, wd)[0].reshape(-1, 2)  # (N, 2): x, y
    vals = flow.reshape(-1, 2)
    land = grid + vals
    x1, y1 = land[:, 0], land[:, 1]
    valid = (x1 > 0) & (x1 < wd) & (y1 > 0) & (y1 < ht)
    rows = []
    for lo in range(0, grid.shape[0], SPLAT_BLOCK):
        q = grid[lo : lo + SPLAT_BLOCK]
        d2 = (q[:, 0:1] - x1[None]) ** 2 + (q[:, 1:2] - y1[None]) ** 2
        nearest = jnp.argmin(jnp.where(valid[None], d2, jnp.inf), axis=1)
        rows.append(vals[nearest])
    out = jnp.concatenate(rows).reshape(ht, wd, 2)
    return jnp.where(valid.any(), out, jnp.zeros_like(out))


_splat = jax.jit(forward_interpolate)


def flow_with_init(ref: Reference, variables, image1, image2, iters: int, flow_init=None):
    """``(flow_lr, flow_up)`` of the test-mode forward for NHWC float32 images
    in [0, 255] whose sides divide by 8; ``flow_init`` (B, H/8, W/8, 2) is
    added to the initial coordinates."""
    image1 = jnp.asarray(image1, jnp.float32)
    image2 = jnp.asarray(image2, jnp.float32)
    pyr, net, inp = ref._encode(variables, image1, image2)
    b, h, w, _ = image1.shape
    coords0 = _coords(b, h // 8, w // 8)
    coords1 = coords0 if flow_init is None else coords0 + jnp.asarray(flow_init, jnp.float32)
    mask = None
    for _ in range(iters):
        net, mask, coords1 = ref._step(variables, pyr, net, inp, coords1)
    return coords1 - coords0, ref._upsample(variables, net, mask, coords1)


def reference_session(ref: Reference, variables, frames, iters: int, warm_start: bool = True) -> list:
    """The native-shape (H, W, 2) float32 flows of the pairs (frames[t],
    frames[t+1]) of one session, in order. ``warm_start=False`` is the
    control that drops the mechanism: every pair starts cold."""
    h, w = frames[0].shape[:2]
    flows, flow_prev = [], None
    for t in range(len(frames) - 1):
        p1, (top, left) = pad_sintel(np.asarray(frames[t], np.float32))
        p2, _ = pad_sintel(np.asarray(frames[t + 1], np.float32))
        init = None
        if warm_start and flow_prev is not None:
            init = _splat(flow_prev)[None]
        flow_lr, flow_up = flow_with_init(ref, variables, p1[None], p2[None], iters, init)
        flow_prev = flow_lr[0]
        flows.append(np.asarray(jax.device_get(flow_up))[0, top : top + h, left : left + w])
    return flows
