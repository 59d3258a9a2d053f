"""Driver ``eval_pass``: validation passes through the program's own entry
(``ShapeCachedForward`` + ``evaluation._run_metric_pass``: double-buffered
input pipeline, metrics folded on the device, one pull at the end of a
pass), repeated until the window is spent; the last pass finishes.

Closed loop by nature. The window is a whole number of passes, and the rate
is all pairs of those passes over all their time.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import traffic_gen
from benchmark.checks import control_gaps, flops_info
from benchmark.harness import compared, emit
from benchmark.program import build_model, executable_memory
from benchmark.reference.raft import Reference, reference_flow
from benchmark.trace_reduce import SPAN_PREFIX


class PoolDataset:
    """``length`` samples that cycle a pool held in memory."""

    def __init__(self, pool: list, length: int):
        self.pool, self.length = pool, int(length)

    def __len__(self) -> int:
        return self.length

    def sample(self, index: int) -> dict:
        return self.pool[index % len(self.pool)]


def _pass(state, dataset) -> np.ndarray:
    from raft_ncup_tpu.evaluation import _run_metric_pass

    t = state["traffic"]
    return _run_metric_pass(
        state["fwd"], dataset, kind=t["metric_kind"], iters=int(t["iters"]),
        batch_size=int(t["batch_size"]), pad_mode=t["pad_mode"],
        num_workers=int(t["num_workers"]), depth=int(t["depth"]),
    )


def _build(cell) -> dict:
    from raft_ncup_tpu.inference.pipeline import ShapeCachedForward

    t = cell.traffic
    ref = Reference(cell.config["model"])
    variables = ref.init_variables(cell.seed)
    model = build_model(cell.config["model"])
    return {
        "cell": cell, "traffic": t, "ref": ref, "variables": variables,
        "pool": traffic_gen.make_pool(t, cell.seed),
        "fwd": ShapeCachedForward(model, variables),
    }


def setup(cell) -> dict:
    state = _build(cell)
    # warm-up: one batch through the very pass the window runs.
    _pass(state, PoolDataset(state["pool"], int(state["traffic"]["batch_size"])))
    return state


def run(state, seconds: float) -> dict:
    t = state["traffic"]
    dataset = PoolDataset(state["pool"], int(t["pairs_per_pass"]))
    accs, t0 = [], time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "eval_pass"):
            accs.append(_pass(state, dataset))
    window_s = time.perf_counter() - t0
    pairs = len(accs) * len(dataset)
    acc = np.sum(accs, axis=0)
    h, w = t["native_hw"]
    info = flops_info(state["cell"].config["model"], (h, w), int(t["iters"]), pairs, window_s)
    return {
        "window_s": window_s, "attempted": pairs, "failed": 0,
        "end_to_end": {"pairs_per_s": pairs / window_s},
        "pairs": pairs, "passes": len(accs), "generator_lateness_s": 0.0,
        "executable_memory": executable_memory(state["fwd"]),
        "window_mean_epe_vs_synthetic_gt_px": float(acc[0] / acc[1]),
        "keep": {"acc": acc, "px_expected": float(pairs * h * w)},
        **info,
    }


def _sample(cell, pool: list) -> list:
    """The pool indices the check compares: ``check_pairs`` distinct ones,
    drawn from the seed."""
    return traffic_gen.sample_indices(cell.seed, len(pool), int(cell.traffic["check_pairs"]))


def check(state, window: dict) -> list:
    acc = window["keep"]["acc"]
    return [
        compared("window_px_count_gap", abs(float(acc[1]) - window["keep"]["px_expected"]), 0),
        compared("window_nonfinite_sums", int(np.sum(~np.isfinite(acc))), 0),
        _gap_to_reference(state),
    ]


def _gap_to_reference(state) -> dict:
    """One batch more through the window's own pass and executable, filled
    with the sampled pairs, their ground truth replaced by the reference's
    flow: the accumulator the pass returns is then the gap between the two."""
    t, cell = state["traffic"], state["cell"]
    picks = _sample(cell, state["pool"])
    sample, mags = [], []
    for i in picks:
        pair = state["pool"][i]
        flow = reference_flow(
            state["ref"], state["variables"], pair["image1"], pair["image2"], int(t["iters"])
        )
        mags.append(float(np.abs(flow).mean()))
        sample.append({**pair, "flow": flow})
    gap = _pass(state, PoolDataset(sample, int(t["batch_size"])))
    mean_gap = float(gap[0] / gap[1])
    emit({
        "phase": "reference", "sampled_pool_indices": picks,
        "reference_mean_abs_flow_px": float(np.mean(mags)),
        "flow_gap_mean_px": mean_gap, "share_under_1px": float(gap[2] / gap[1]),
    })
    return compared("flow_gap_mean_px", mean_gap, cell.limit("flow_gap_mean_px"))


def reading(cell, seconds: float) -> list:
    """The program's reading of the number a limit is set from, for
    ``readings.py``: the check's batch alone, which needs no window."""
    return [_gap_to_reference(_build(cell))]


def control(cell) -> list:
    """The control's reading of the number ``check`` compares, on the pairs
    ``check`` samples for this seed (their mean, as the accumulator gives)."""
    t = cell.traffic
    gaps = control_gaps(cell, int(t["iters"]), int(t["check_pairs"]))
    return [compared("flow_gap_mean_px", float(np.mean(gaps)), cell.limit("flow_gap_mean_px"))]


def close(state) -> None:
    pass
