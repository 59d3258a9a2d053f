#!/usr/bin/env python3
"""Readings for the limits of ``correct``: one cell's compared numbers over
many seeds in ONE process.

    python3 benchmark/readings.py --workload <name> --seeds 1,2,3 [--seconds 1]
        [--control] [--model-precision bf16_infer]

Without a switch: the program as configured. ``--control``: the reference in
the program's place at the configuration's control precision (``high``, the
nearest below the ``highest`` it states). ``--model-precision``: the program
with another of its own precision presets, the lower-precision path a later
PR could be tempted to switch on.

Prints one JSON line per seed with every number compared, and a last line
with the largest and smallest of each. Run on the chip at the cell's own
size; the limits in ``benchmark/limits/<workload>.json`` are set from these
lines (PERF.md section 2). The benchmark's own runs never call this.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    import argparse

    from benchmark import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--model-precision")
    args = ap.parse_args(argv)
    what = "control" if args.control else args.model_precision or "program"

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    seen: dict = {}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(harness.ROOT, bench, args.workload, seed)
        if n == 0:
            harness.setup_jax(cell, require_tpu=True)
        if args.model_precision:
            cell.config["model"]["precision"] = args.model_precision
        t0 = time.perf_counter()
        checks = cell.driver.control(cell) if args.control else cell.driver.reading(cell, args.seconds)
        row = {c["check"]: c["value"] for c in checks}
        harness.emit({"seed": seed, "what": what, "seconds": time.perf_counter() - t0, **row})
        for k, v in row.items():
            seen.setdefault(k, []).append(v)
    harness.emit({"workload": args.workload, "what": what,
                  "largest": {k: max(v) for k, v in seen.items()},
                  "smallest": {k: min(v) for k, v in seen.items()}})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
