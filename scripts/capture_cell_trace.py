#!/usr/bin/env python
"""Capture one benchmark cell's window through the program's own
``utils/profiling.trace`` and print the per-scope reduction of the capture.

The benchmark's harness deletes its trace before anything of the program can
read it (PERF.md section 7, first item), so until a ``benchmark`` issue wires
the per-scope reduction in, this is how a PR shows where a cell's device time
goes and what the host did in its gaps: the cell's own driver, configuration
and traffic (``benchmark/drivers/``), a window of ``--seconds``, the capture
left under ``--out`` with its ``op_scopes.json``, and the report of
``scripts/device_trace_report.py`` beside the busy time
``benchmark/trace_reduce.py`` reads from the same file. TPU only, like the
benchmark; the numbers are of a traced window and are never end-to-end
metrics.

Usage (on the chip):
    python scripts/capture_cell_trace.py --workload eval_sintel_nc --out chiprun_out/cap_eval
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--out", required=True, help="directory for the capture")
    parser.add_argument("--seed", type=int, default=3000000017)
    parser.add_argument("--seconds", type=float, default=6.0)
    args = parser.parse_args(argv)

    from benchmark import harness, trace_reduce

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell(ROOT, bench, args.workload, args.seed)
    harness.setup_jax(cell, require_tpu=True)

    from raft_ncup_tpu.observability import get_telemetry
    from raft_ncup_tpu.utils.profiling import device_trace_report, find_xplane, trace

    state = cell.driver.setup(cell)
    try:
        get_telemetry().reset()  # the process hub's spans of the window alone
        with trace(args.out):
            window = cell.driver.run(state, args.seconds)
    finally:
        cell.driver.close(state)

    report = device_trace_report(args.out)
    ops, spans, _ = trace_reduce.read_xplane(find_xplane(args.out))
    theirs = trace_reduce.reduce(ops, spans)
    print(json.dumps({
        "workload": args.workload,
        "pairs": window.get("pairs"),
        "window_s": window["window_s"],
        "benchmark_trace_reduce_busy_s": theirs["busy_s"],
        "driver_report_stages": window.get("report", {}).get("stages"),
        "process_hub_stages": get_telemetry().tracer.stage_summary(),
        "process_hub_counters": get_telemetry().registry.snapshot()["counters"],
        **report,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
