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

``--from_setup`` starts the capture before the driver's set-up, so the
report's idle gaps during set-up carry the start-up phases' labels
(``startup_trace_lower``, ``startup_compile``, ``startup_first_run``,
``startup_weights``, ``startup_warmup``, ``input_start``) on the device's
clock; ``startup_report()`` is printed in the JSON either way.

Usage (on the chip):
    python scripts/capture_cell_trace.py --workload eval_sintel_nc --out chiprun_out/cap_eval
"""

from __future__ import annotations

import argparse
import contextlib
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
    parser.add_argument(
        "--from_setup", action="store_true",
        help="capture the driver's set-up too, not the window alone",
    )
    args = parser.parse_args(argv)

    from benchmark import harness, trace_reduce

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell(ROOT, bench, args.workload, args.seed)
    harness.setup_jax(cell, require_tpu=True)

    from raft_ncup_tpu.observability import get_telemetry, startup_report
    from raft_ncup_tpu.utils.profiling import device_trace_report, find_xplane, trace

    with contextlib.ExitStack() as capture:
        if args.from_setup:
            capture.enter_context(trace(args.out))
        state = cell.driver.setup(cell)
        try:
            if not args.from_setup:
                get_telemetry().reset()  # the process hub's spans of the window alone
                capture.enter_context(trace(args.out))
            window = cell.driver.run(state, args.seconds)
            capture.close()
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
        "startup": startup_report(),
        **report,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
