"""The one general generator of inputs: seeded frame pairs with ground
truth, from the parameters of a traffic file.

A pair is a smooth random texture, a smooth random flow field and the
backward-warped second frame (the recipe of the program's
``data/synthetic.make_pair``, copied here so the benchmark owns its inputs).
Every seed draws the same number of pairs of the same size.
"""

from __future__ import annotations

import cv2
import numpy as np

cv2.setNumThreads(0)


def _smooth_noise(rng, hw, scale: int, channels: int) -> np.ndarray:
    h, w = hw
    low = rng.normal(size=(max(2, h // scale), max(2, w // scale), channels))
    return cv2.resize(
        low.astype(np.float32), (w, h), interpolation=cv2.INTER_CUBIC
    ).reshape(h, w, channels)


def make_pair(rng: np.random.Generator, hw, max_flow_px: float) -> dict:
    """uint8 ``image1``/``image2`` (H, W, 3) and float32 ``flow`` (H, W, 2)
    with image2(x) = image1(x - flow(x))."""
    h, w = hw
    tex = _smooth_noise(rng, (h, w), 8, 3)
    img1 = ((tex - tex.min()) / (np.ptp(tex) + 1e-6) * 255.0).astype(np.uint8)
    flow = (_smooth_noise(rng, (h, w), 32, 2) * (max_flow_px / 2.0)).astype(np.float32)
    xx, yy = np.meshgrid(np.arange(w, dtype=np.float32), np.arange(h, dtype=np.float32))
    img2 = cv2.remap(
        img1, xx - flow[..., 0], yy - flow[..., 1], cv2.INTER_LINEAR,
        borderMode=cv2.BORDER_REFLECT,
    )
    return {"image1": img1, "image2": img2, "flow": flow}


def make_pool(traffic: dict, seed: int) -> list:
    """``traffic["pool"]`` pairs of ``traffic["native_hw"]`` from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x706F6F6C]))
    hw = tuple(traffic["native_hw"])
    return [
        make_pair(rng, hw, float(traffic.get("max_flow_px", 12.0)))
        for _ in range(int(traffic["pool"]))
    ]


def sample_indices(seed: int, n_from: int, k: int) -> list:
    """k distinct indices of range(n_from), drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x73616D70]))
    return sorted(int(i) for i in rng.choice(n_from, size=min(k, n_from), replace=False))
