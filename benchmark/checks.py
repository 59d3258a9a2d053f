"""What the drivers of flow cells share: the analytic utilisation line and
the control's reading of ``flow_gap_mean_px``."""

from __future__ import annotations

import jax
import numpy as np

from benchmark import flops, meters, traffic_gen
from benchmark.reference.raft import Reference, reference_flow


def flops_info(model: dict, native_hw, iters: int, pairs: int, window_s: float) -> dict:
    """Operations one pair needs at the padded shape, and on a TPU the share
    of the chip's peak that ``pairs`` in ``window_s`` amount to. An info
    line, not a metric."""
    h, w = native_hw
    per_pair = flops.forward_flops(model, 1, -(-h // 8) * 8, -(-w // 8) * 8, iters)
    info = {"analytic_flops_per_pair": per_pair}
    device = jax.devices()[0]
    if device.platform == "tpu":
        peak = meters.load_peaks(device.device_kind)["flops_per_s"]
        info["analytic_flops_utilisation_pct"] = 100.0 * per_pair * pairs / window_s / peak
    return info


def control_gaps(cell, iters: int, picks: int) -> list:
    """Per sampled pair of the seed's pool: the mean endpoint gap (px) between
    the reference at the configuration's control precision (``high``, the
    nearest below the ``highest`` it states) and the reference proper. The
    reference stands in the program's place; run by ``readings.py`` only."""
    ref = Reference(cell.config["model"])
    low = Reference(cell.config["model"], precision=cell.config["control"]["reference_precision"])
    variables = ref.init_variables(cell.seed)
    pool = traffic_gen.make_pool(cell.traffic, cell.seed)
    gaps = []
    for i in traffic_gen.sample_indices(cell.seed, len(pool), picks):
        a, b = (
            reference_flow(r, variables, pool[i]["image1"], pool[i]["image2"], iters)
            for r in (ref, low)
        )
        gaps.append(float(np.sqrt(((a - b) ** 2).sum(-1)).mean()))
    return gaps
