"""Driver ``serve_closed``: the program's ``FlowServer`` in this process,
under a closed loop of ``clients`` threads. Each client submits one seeded
frame pair, waits on the handle, checks the answer and submits the next at
once; after ``seconds`` no client starts a new request, and the window
closes when the last answer is in. The window opens with every client's
first request already queued (the server's ``pause()``/``resume()`` around the
first round), so that the first batches do not depend on which thread wins
the race to the queue. The rate is all answers over all that
time; the tail is over all requests. A request that is not ``ok`` counts as
failed and as a latency of the whole window.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from benchmark import meters, traffic_gen
from benchmark.checks import control_gaps, flops_info
from benchmark.harness import compared, emit
from benchmark.program import build_model, executable_memory
from benchmark.reference.raft import Reference, reference_flow
from benchmark.trace_reduce import SPAN_PREFIX


def setup(cell) -> dict:
    from raft_ncup_tpu.config import ServeConfig
    from raft_ncup_tpu.observability import Telemetry
    from raft_ncup_tpu.serving.server import FlowServer

    t = cell.traffic
    ref = Reference(cell.config["model"])
    variables = ref.init_variables(cell.seed)
    model = build_model(cell.config["model"])
    telemetry = Telemetry()
    server = FlowServer(
        model, variables,
        ServeConfig(
            batch_sizes=tuple(t["batch_sizes"]), iter_levels=tuple(t["iter_levels"]),
            queue_capacity=int(t["queue_capacity"]),
        ),
        telemetry=telemetry,
    )
    server.warmup(tuple(t["native_hw"]))
    # warmup() runs every program of the cell once; the host path's first
    # use (threads, pad buffers) is milliseconds beside answers of seconds
    # and is left to the window rather than paid as one more round here.
    return {
        "cell": cell, "traffic": t, "ref": ref, "variables": variables,
        "pool": traffic_gen.make_pool(t, cell.seed), "server": server,
        "telemetry": telemetry,
    }


def _closed_loop(state, seconds: float) -> dict:
    """Every client sends at least one request; none starts one after
    ``seconds``. Returns latencies, failures and the last flow per pool
    index."""
    t, server, pool = state["traffic"], state["server"], state["pool"]
    n_clients = int(t["clients"])
    lat: list = [[] for _ in range(n_clients)]
    bad = [0] * n_clients
    flows: dict = {}
    queued = threading.Barrier(n_clients + 1)
    server.pause()
    t0 = time.perf_counter()

    def client(k: int) -> None:
        rng = np.random.default_rng(np.random.SeedSequence([state["cell"].seed, 0xC11E, k]))
        first = True
        while True:
            for i in rng.permutation(len(pool)):
                pair = pool[int(i)]
                t_sub = time.perf_counter()
                with jax.profiler.TraceAnnotation(SPAN_PREFIX + "client_wait"):
                    handle = server.submit(pair["image1"], pair["image2"])
                    if first:
                        first = False
                        queued.wait()
                    resp = handle.result()
                lat[k].append(time.perf_counter() - t_sub)
                if resp.ok and resp.flow is not None:
                    flows[int(i)] = resp.flow
                else:
                    bad[k] += 1
                if time.perf_counter() - t0 >= seconds:
                    return

    threads = [threading.Thread(target=client, args=(k,), name=f"bench-client-{k}")
               for k in range(n_clients)]
    for th in threads:
        th.start()
    queued.wait()  # every client's first request is in the queue
    server.resume()
    for th in threads:
        th.join()
    return {
        "window_s": time.perf_counter() - t0,
        "latencies": [x for per in lat for x in per], "failed": sum(bad),
        "flows": flows,
    }


def run(state, seconds: float) -> dict:
    t, cell = state["traffic"], state["cell"]
    state["telemetry"].registry.reset()  # the stage histograms of the window alone
    w = _closed_loop(state, seconds)
    report = state["server"].report()
    n, window_s = len(w["latencies"]), w["window_s"]
    done = n - w["failed"]
    # a failed request counts as the whole window in the tail
    tail = sorted(w["latencies"])[:done] + [window_s] * w["failed"]
    info = flops_info(
        cell.config["model"], t["native_hw"], int(t["iter_levels"][0]), done, window_s
    )
    return {
        "window_s": window_s, "attempted": n, "failed": w["failed"],
        "end_to_end": {
            "pairs_per_s": done / window_s,
            "latency_p95_ms": 1000.0 * meters.nearest_rank(tail, 0.95),
        },
        "pairs": done, "latency_p50_ms": 1000.0 * meters.nearest_rank(tail, 0.50),
        "latency_max_ms": 1000.0 * max(tail), "latency_samples": n,
        "generator_lateness_s": 0.0,
        # the server has no accessor for its cost ledger (PERF.md section 7)
        "executable_memory": executable_memory(state["server"]._fwd),
        "report": {"stages": report["stages"]}, "executables": report["executables"],
        "keep": {"flows": w["flows"]}, **info,
    }


def check(state, window: dict) -> list:
    """A seeded sample of the answers the window delivered, each against the
    reference's flow of the same pair."""
    t, cell = state["traffic"], state["cell"]
    flows = window["keep"]["flows"]
    served = sorted(flows)
    picks = [served[i] for i in traffic_gen.sample_indices(cell.seed, len(served), int(t["check_pairs"]))]
    gaps, worst, mags, bad = [], 0.0, [], 0
    for i in picks:
        pair = state["pool"][i]
        want = reference_flow(
            state["ref"], state["variables"], pair["image1"], pair["image2"],
            int(t["iter_levels"][0]),
        )
        got = np.asarray(flows[i], np.float32)
        bad += int(got.shape != want.shape or not np.isfinite(got).all())
        epe = np.sqrt(((got - want) ** 2).sum(-1))
        gaps.append(float(epe.mean()))
        worst = max(worst, float(epe.max()))
        mags.append(float(np.abs(want).mean()))
    emit({
        "phase": "reference", "sampled_pool_indices": picks,
        "reference_mean_abs_flow_px": float(np.mean(mags)),
        "flow_gap_mean_px": float(np.mean(gaps)), "flow_gap_max_px": worst,
    })
    return [
        compared("answers_malformed", bad, 0),
        compared("flow_gap_mean_px", float(np.max(gaps)), cell.limit("flow_gap_mean_px")),
    ]


def reading(cell, seconds: float) -> list:
    """The program's reading of the numbers a limit is set from, for
    ``readings.py``: a short window at the cell's own load, then the check."""
    state = setup(cell)
    try:
        window = run(state, seconds)
        return [compared("failed", window["failed"], 0)] + check(state, window)
    finally:
        close(state)


def control(cell) -> list:
    """The control's reading of the number ``check`` compares: the smallest
    of its sample, where ``check`` holds the largest of its own to the limit."""
    t = cell.traffic
    gaps = control_gaps(cell, int(t["iter_levels"][0]), int(t["check_pairs"]))
    return [compared("flow_gap_mean_px", float(np.min(gaps)), cell.limit("flow_gap_mean_px"))]


def close(state) -> None:
    state["server"].drain(timeout=60)
