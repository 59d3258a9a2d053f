"""The data-driven harness: one cell, once, in this process.

Everything that belongs to one configuration, one traffic mix, one driver or
one per-layer metric is a file found by the name ``BENCHMARK.json`` gives:

- ``configs[].file``                      the configuration as it is run
- ``benchmark/traffic/<traffic>.json``    the traffic mix (names its driver)
- ``benchmark/drivers/<driver>.py``       setup / run / check / close
- ``benchmark/layer_metrics/<metric>.py`` ``read(run) -> number | None``
- ``benchmark/limits/<workload>.json``    the limits ``correct`` is held to

so a later PR adds a cell by adding entries and files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".cache", "benchmark", "xla")
TRACE_DIR = os.path.join(ROOT, ".cache", "benchmark", "trace")


class NoResult(Exception):
    """The run cannot print a result line; the process exits non-zero."""


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A driver or a reader, by file: metric names hold dots, which a plain
    import cannot spell."""
    name = "benchmark_file_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise NoResult(f"no such file: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(entries: list, workload: str) -> list:
    """The metrics of one list that this cell reports: an entry without a
    ``workloads`` key is reported by every cell."""
    return [m for m in entries if workload in m.get("workloads", [workload])]


class Cell:
    """What a driver gets: the resolved files of one cell and the seed."""

    def __init__(self, root: str, bench: dict, name: str, seed: int):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise NoResult(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
        self.root = root
        self.bench = bench
        self.workload = cells[name]
        self.name = name
        self.seed = int(seed)
        entry = {c["name"]: c for c in bench["configs"]}[self.workload["config"]]
        self.config = load_json(os.path.join(root, entry["file"]))
        base = os.path.join(root, bench["paths"][0])
        self.traffic = load_json(
            os.path.join(base, "traffic", self.workload["traffic"] + ".json")
        )
        limits = os.path.join(base, "limits", name + ".json")
        self.limits = load_json(limits)["limits"] if os.path.isfile(limits) else {}
        self.driver = load_module(
            os.path.join(base, "drivers", self.traffic["driver"] + ".py")
        )
        self.readers_dir = os.path.join(base, "layer_metrics")

    def limit(self, number: str) -> float:
        if number not in self.limits:
            raise NoResult(
                f"benchmark/limits/{self.name}.json sets no limit for {number!r}"
            )
        return float(self.limits[number])


def compared(name: str, value: float, limit: float) -> dict:
    """One number beside its limit. Not-a-number never passes."""
    ok = bool(value == value and value <= limit)
    return {"check": name, "value": value, "limit": limit, "ok": ok}


def setup_jax(cell: Cell, require_tpu: bool):
    """Import jax once, refuse anything but the chips the cell asks for,
    point the persistent compilation cache at a fixed directory inside the
    checkout (or leave it where ``JAX_COMPILATION_CACHE_DIR`` says), and set
    the jax options the configuration's ``runtime`` section states."""
    import jax

    chips = int(cell.workload["chips"])
    for option, value in cell.config.get("runtime", {}).items():
        jax.config.update(option, value)

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and require_tpu:
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoResult(f"jax found no device: {e}")
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoResult(
                f"the benchmark measures on a TPU; jax reports {devices[0].platform!r}"
            )
        if len(devices) < chips:
            raise NoResult(f"the cell asks for {chips} chips, jax has {len(devices)}")
    return jax, devices[:chips]


def run_cell(
    workload: str, seed: int, seconds: float, trace: int, *, t_start: float,
    root: str = ROOT, require_tpu: bool = True,
) -> dict:
    """Drive one cell and return the object of the last line (also printed,
    with the detail lines before it). ``require_tpu=False`` is for the CPU
    rehearsals under tests/benchmark only; the command line has no such
    switch."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = Cell(root, bench, workload, seed)
    jax, devices = setup_jax(cell, require_tpu)

    from benchmark import meters, trace_reduce

    meter = meters.CompileMeter()
    state = cell.driver.setup(cell)
    try:
        at_setup = meter.snapshot()
        setup_s = time.perf_counter() - t_start
        emit({"phase": "setup", "setup_s": setup_s, **at_setup})

        if trace:
            seconds = min(float(seconds), float(cell.traffic.get("trace_seconds", 6)))
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            os.makedirs(TRACE_DIR, exist_ok=True)
            jax.profiler.start_trace(TRACE_DIR)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + "window"):
                window = cell.driver.run(state, float(seconds))
        finally:
            if trace:
                jax.profiler.stop_trace()
        at_close = meter.snapshot()
        device = meters.device_record(devices)
        emit({"phase": "memory", "memory_stats": devices[0].memory_stats()})
        emit({"phase": "window", **{k: v for k, v in window.items() if k != "keep"}})

        checks = [
            compared(
                "compile_events_in_window",
                at_close["compiles"] - at_setup["compiles"], 0,
            ),
            compared("failed", window["failed"], 0),
        ]
        t0 = time.perf_counter()
        checks += cell.driver.check(state, window)
        for c in checks:
            emit(c)
        emit({"phase": "check", "seconds": time.perf_counter() - t0})
    finally:
        cell.driver.close(state)

    run = {
        "window": window,
        "setup": {"setup_s": setup_s, **at_setup},
        "report": window.get("report", {}),
    }
    if trace:
        reduced = trace_reduce.reduce_trace_dir(TRACE_DIR)
        emit({"phase": "trace", "layout": reduced.pop("layout")})
        run["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        metrics = {}
        for m in metrics_of(bench["per_layer"], workload):
            reader = load_module(os.path.join(cell.readers_dir, m["name"] + ".py"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, **window["end_to_end"]}
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(bench["end_to_end"], workload)
        }

    result = {
        "correct": all(c["ok"] for c in checks),
        "attempted": window["attempted"],
        "failed": window["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": run["trace"]["device_ops"],
            "idle_gaps": run["trace"]["idle_gaps"],
        }
    emit(result)
    return result


def main(argv: list, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        run_cell(args.workload, args.seed, args.seconds, args.trace, t_start=t_start)
    except NoResult as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    return 0
