"""Driver ``eval_pass_kitti``: KITTI-2015 validation passes through the
program's own entry (``evaluation._run_metric_pass`` as ``validate_kitti``
calls it: metric kind ``kitti``, pad mode ``kitti``, a sparse ``valid`` mask,
the samples grouped by native size across the stream and every size's
remainder filled to a whole batch), repeated until the window is spent; the
last pass finishes. ``eval_pass``'s shape; what differs is the data and what
``correct`` holds.

**The data**, all from ``--seed``: ``pool_per_size`` pairs at each of the
traffic file's native sizes (``traffic_gen.make_pair``), each with a mask that
is 1 with probability ``valid_density`` below the frame's upper
``valid_from_row_share`` and 0 above. A pass is the traffic's ``pairs`` of each
size in ONE seeded permutation, the same every pass; the n-th pair of a size
in that order is the pool's n-th of that size, cycled.

**``correct``**, beside the harness's two rows, all exact (limit 0):

- ``window_frames_gap``: the accumulator's frame count against the real
  pairs of the window's passes (a fill row counted as a frame reads over 0);
- ``window_valid_px_gap``: its valid-pixel count against the pool's own
  masks over the same samples (a mask dropped or thresholded otherwise, or
  a fill row's mask not zero, reads over 0; a pass's count stays under
  2^24, which float32 counts exactly). The count is of the mask alone: a
  pad or an in-graph crop at another offset leaves it as it is, and is held
  by ``flow_gap_mean_px`` below, which is read in EVERY size for that;
- ``window_nonfinite_sums``;
- ``window_program_builds``: the window's ``compiles`` + ``evictions`` of
  ``ShapeCachedForward.stats`` (every size was warmed in set-up);
- ``window_fill_rows_gap``: the window's ``eval_fill_rows_total`` against
  what the sizes' remainders give (16 a pass at batch 8);

and ``flow_gap_mean_px``: for EACH native size one batch more through the
window's own pass and executable, ``check_pairs_per_size`` sampled pairs of
that size with ``benchmark/reference/raft_kitti.py``'s flow as ground truth
under the pool's own sparse mask (the rest of the batch is the pass's fill
rows); the accumulator's per-frame mean is then the gap between the two over
the valid pixels, and the worst of the sizes is compared.

A program whose passes do not group by size (the parent of PR 45 under this
PR's benchmark files) is refused at once, before anything compiles: it would
build a program a (size, run length) inside the window.
"""

from __future__ import annotations

import inspect
import time

import jax
import numpy as np

from benchmark import traffic_gen
from benchmark.checks import flops_info
from benchmark.drivers.eval_pass import PoolDataset
from benchmark.harness import NoResult, compared, emit
from benchmark.program import build_model, executable_memory
from benchmark.reference.raft_kitti import KittiReference, reference_flow_kitti
from benchmark.trace_reduce import SPAN_PREFIX

COUNTERS = ("eval_pairs_total", "eval_rows_total", "eval_fill_rows_total")


def _dataset(samples: list) -> PoolDataset:
    """The samples of one pass, in their order."""
    return PoolDataset(samples, len(samples))


def _sizes(traffic: dict) -> list:
    return [tuple(s["native_hw"]) for s in traffic["sizes"]]


def make_pool(traffic: dict, seed: int) -> dict:
    """``{native (h, w): [pool_per_size samples]}``: uint8 frames, float32
    ``flow`` and a float32 0/1 ``valid`` that is empty above the frame's
    upper ``valid_from_row_share``."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x6B697474]))
    density, sky = float(traffic["valid_density"]), float(traffic["valid_from_row_share"])
    pool = {}
    for h, w in _sizes(traffic):
        pool[(h, w)] = []
        for _ in range(int(traffic["pool_per_size"])):
            pair = traffic_gen.make_pair(rng, (h, w), float(traffic["max_flow_px"]))
            valid = (rng.random((h, w)) < density).astype(np.float32)
            valid[: int(h * sky)] = 0.0
            pool[(h, w)].append({**pair, "valid": valid})
    return pool


def pass_samples(traffic: dict, seed: int, pool: dict) -> list:
    """The samples of a pass: each size's ``pairs`` in one seeded
    permutation, the n-th of a size the pool's n-th of that size, cycled."""
    sizes = [hw for s in traffic["sizes"] for hw in [tuple(s["native_hw"])] * int(s["pairs"])]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x6F726465]))
    seen: dict = {}
    out = []
    for i in rng.permutation(len(sizes)):
        hw = sizes[int(i)]
        out.append(pool[hw][seen.get(hw, 0) % len(pool[hw])])
        seen[hw] = seen.get(hw, 0) + 1
    return out


def fill_rows_a_pass(traffic: dict) -> int:
    batch = int(traffic["batch_size"])
    return sum((-int(s["pairs"])) % batch for s in traffic["sizes"])


def _pass(state, dataset) -> np.ndarray:
    from raft_ncup_tpu.evaluation import _run_metric_pass

    t = state["traffic"]
    return _run_metric_pass(
        state["fwd"], dataset, kind=t["metric_kind"], iters=int(t["iters"]),
        batch_size=int(t["batch_size"]), pad_mode=t["pad_mode"], with_valid=True,
        num_workers=int(t["num_workers"]), depth=int(t["depth"]),
    )


def _refuse_without_size_groups() -> None:
    from raft_ncup_tpu.inference import pipeline

    if "fill_valid" not in inspect.signature(pipeline.uniform_batches).parameters:
        raise NoResult(
            "this program's validation pass cuts a group at every change of size "
            "(uniform_batches has no fill_valid): a pass over mixed sizes builds a "
            "program a (size, run length), inside the window too"
        )


def _build(cell) -> dict:
    from raft_ncup_tpu.inference.pipeline import ShapeCachedForward

    _refuse_without_size_groups()
    t = cell.traffic
    ref = KittiReference(cell.config["model"])
    variables = ref.init_variables(cell.seed)
    model = build_model(cell.config["model"])
    pool = make_pool(t, cell.seed)
    return {
        "cell": cell, "traffic": t, "ref": ref, "variables": variables, "pool": pool,
        "samples": pass_samples(t, cell.seed, pool),
        "fwd": ShapeCachedForward(model, variables),
    }


def setup(cell) -> dict:
    state = _build(cell)
    # warm-up: one pair a size through the very pass the window runs; the
    # pass fills each to a whole batch, so these are the window's programs.
    _pass(state, _dataset([state["pool"][hw][0] for hw in _sizes(state["traffic"])]))
    return state


def _counters() -> dict:
    from raft_ncup_tpu.observability import get_telemetry

    hub = get_telemetry()
    return {name: hub.counter_value(name) for name in COUNTERS}


def _flops_info(state, passes: int, window_s: float) -> dict:
    """``checks.flops_info`` a native size, put together: operations a pair
    of the pass needs at its padded size (the mean over the pass's pairs)
    and on a TPU the share of the chip's peak the window's real pairs amount
    to. An info line, not a metric."""
    t, model = state["traffic"], state["cell"].config["model"]
    by_size = [
        (int(s["pairs"]), flops_info(model, s["native_hw"], int(t["iters"]), passes * int(s["pairs"]), window_s))
        for s in t["sizes"]
    ]
    info = {
        "analytic_flops_per_pair":
            sum(n * i["analytic_flops_per_pair"] for n, i in by_size) / sum(n for n, _ in by_size)
    }
    if all("analytic_flops_utilisation_pct" in i for _, i in by_size):
        info["analytic_flops_utilisation_pct"] = sum(i["analytic_flops_utilisation_pct"] for _, i in by_size)
    return info


def run(state, seconds: float) -> dict:
    dataset = _dataset(state["samples"])
    fwd = state["fwd"]
    before, built = _counters(), dict(fwd.stats)
    accs, t0 = [], time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "eval_pass"):
            accs.append(_pass(state, dataset))
    window_s = time.perf_counter() - t0
    counters = {k: v - before[k] for k, v in _counters().items()}
    builds = {k: fwd.stats[k] - built[k] for k in ("compiles", "evictions")}
    passes, pairs = len(accs), len(accs) * len(dataset)
    acc = np.sum(accs, axis=0)
    return {
        "window_s": window_s, "attempted": pairs, "failed": 0,
        "end_to_end": {"pairs_per_s": pairs / window_s},
        "pairs": pairs, "passes": passes, "generator_lateness_s": 0.0,
        "rows": counters["eval_rows_total"], "fill_rows": counters["eval_fill_rows_total"],
        "programs_resident": fwd.stats["compiles"] - fwd.stats["evictions"],
        "executable_memory": executable_memory(fwd),
        "window_epe_vs_synthetic_gt_px": float(acc[0] / acc[1]),
        "window_f1_vs_synthetic_gt_pct": 100.0 * float(acc[2] / acc[3]),
        "report": {"counters": counters, "executables": builds, "passes": passes},
        "keep": {
            "acc": acc,
            "valid_px_expected": passes * float(sum(s["valid"].sum() for s in state["samples"])),
        },
        **_flops_info(state, passes, window_s),
    }


def check(state, window: dict) -> list:
    acc, report = window["keep"]["acc"], window["report"]
    fill_expected = window["passes"] * fill_rows_a_pass(state["traffic"])
    return [
        compared("window_frames_gap", abs(float(acc[1]) - window["pairs"]), 0),
        compared("window_valid_px_gap", abs(float(acc[3]) - window["keep"]["valid_px_expected"]), 0),
        compared("window_nonfinite_sums", int(np.sum(~np.isfinite(acc))), 0),
        compared("window_program_builds", sum(report["executables"].values()), 0),
        compared("window_fill_rows_gap", abs(report["counters"]["eval_fill_rows_total"] - fill_expected), 0),
        _gap_to_reference(state),
    ]


def _picks(cell, pool: dict) -> dict:
    """The pool indices the check compares, a size: ``check_pairs_per_size``
    distinct ones, drawn from the seed."""
    k = int(cell.traffic["check_pairs_per_size"])
    return {hw: traffic_gen.sample_indices(cell.seed, len(pairs), k) for hw, pairs in pool.items()}


def _gap_to_reference(state) -> dict:
    """A size at a time: the sampled pairs with the reference's flow as
    ground truth under their own masks, through the window's own pass (one
    batch, the rest of it fill rows). The worst size is compared."""
    t, cell = state["traffic"], state["cell"]
    by_size, mags, picked = {}, [], _picks(cell, state["pool"])
    for hw, picks in picked.items():
        sample = []
        for i in picks:
            pair = state["pool"][hw][i]
            flow = reference_flow_kitti(
                state["ref"], state["variables"], pair["image1"], pair["image2"], int(t["iters"])
            )
            mags.append(float(np.abs(flow).mean()))
            sample.append({**pair, "flow": flow})
        gap = _pass(state, _dataset(sample))
        # frames counted must be the sampled pairs: a fill row is no frame
        by_size["%dx%d" % hw] = float(gap[0] / gap[1]) if gap[1] == len(sample) else float("nan")
    worst = max(by_size.values(), key=lambda v: float("inf") if v != v else v)
    emit({
        "phase": "reference", "sampled_pool_indices": {"%dx%d" % hw: p for hw, p in picked.items()},
        "reference_mean_abs_flow_px": float(np.mean(mags)),
        "flow_gap_mean_px_by_size": by_size, "flow_gap_mean_px": worst,
    })
    return compared("flow_gap_mean_px", worst, cell.limit("flow_gap_mean_px"))


def reading(cell, seconds: float) -> list:
    """The program's reading of the number a limit is set from, for
    ``readings.py``: the check's batches alone, which need no window."""
    return [_gap_to_reference(_build(cell))]


def control(cell) -> list:
    """The control's reading of the number ``check`` compares: the reference
    at the configuration's control precision (``high``) against the reference
    proper on the pairs ``check`` samples for this seed, per size the mean
    over the frames of the mean gap over the valid pixels (as the accumulator
    gives), the worst size. The control has to read OVER the limit on every
    size for the limit to hold it whatever size a fault shows in, so the
    SMALLEST size is printed too."""
    t = cell.traffic
    ref = KittiReference(cell.config["model"])
    low = KittiReference(cell.config["model"], precision=cell.config["control"]["reference_precision"])
    variables = ref.init_variables(cell.seed)
    pool = make_pool(t, cell.seed)
    by_size = {}
    for hw, picks in _picks(cell, pool).items():
        gaps = []
        for i in picks:
            pair = pool[hw][i]
            a, b = (
                reference_flow_kitti(r, variables, pair["image1"], pair["image2"], int(t["iters"]))
                for r in (ref, low)
            )
            epe = np.sqrt(((a - b) ** 2).sum(-1))
            gaps.append(float(epe[pair["valid"] >= 0.5].mean()))
        by_size["%dx%d" % hw] = float(np.mean(gaps))
    emit({"phase": "control", "flow_gap_mean_px_by_size": by_size})
    limit = cell.limit("flow_gap_mean_px")
    return [
        compared("flow_gap_mean_px", max(by_size.values()), limit),
        compared("flow_gap_mean_px_smallest_size", min(by_size.values()), limit),
    ]


def close(state) -> None:
    pass
