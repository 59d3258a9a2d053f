#!/usr/bin/env python
"""Where a capture's device time went, and what the host did in its gaps.

Reads the newest ``.xplane.pb`` under a ``--trace_dir`` of ``evaluate.py`` or
``serve.py`` (or ``train.py``'s ``<run_dir>/profile``) with the
``op_scopes.json`` the capture left beside it, and prints per device:

- device seconds per ``raft.*`` scope by self time (``raft.refinement`` is
  what runs in the loop and in no inner scope, ``unscoped`` what lies in no
  scope at all: input normalisation, parameter copies); the column sums to
  the busy time, which is the union of the operations' intervals;
- the longest idle gaps, each labelled by the program span that overlaps it
  longest and broken down by every program span that overlaps it
  (``input_wait``, ``input_stage``, ``input_h2d``, ``serve_pad_stage``...:
  the hub's spans, which the bridge puts on the device's clock).

The reduction is ``raft_ncup_tpu/utils/profiling.py``; it needs jax only to
parse the file (``jax.profiler.ProfileData``), no device.

Usage:
    python evaluate.py --dataset sintel ... --trace_dir /tmp/cap
    python scripts/device_trace_report.py /tmp/cap
    python scripts/device_trace_report.py /tmp/cap --json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_ncup_tpu.utils.profiling import device_trace_report  # noqa: E402


def render(report: dict, gaps: int) -> str:
    lines = [f"capture: {report['xplane']}"]
    spans = ", ".join(f"{k} x{v}" for k, v in report["program_spans"].items())
    lines.append(f"program spans on the host plane: {spans or 'none'}")
    if not report["devices"]:
        lines.append("no device plane in this capture (a CPU run has none)")
    for device, r in report["devices"].items():
        busy, window = r["busy_s"], r["window_s"]
        lines.append(
            f"{device}: window {window:.3f} s, busy {busy:.3f} s, "
            f"idle {100.0 * (1.0 - busy / window):.2f}%"
        )
        lines.append(f"  {'scope':<22}{'seconds':>12}{'share of busy':>15}")
        for scope, seconds in r["scope_s"].items():
            lines.append(f"  {scope:<22}{seconds:>12.4f}{100.0 * seconds / busy:>14.2f}%")
        lines.append(f"  {'(sum)':<22}{r['scope_sum_s']:>12.4f}{100.0 * r['scope_sum_s'] / busy:>14.2f}%")
        lines.append("  longest idle gaps (start in window, seconds, label; spans overlapping):")
        for g in r["idle_gaps"][:gaps]:
            parts = ", ".join(
                f"{name} {ov:.3f}"
                for name, ov in sorted(g["spans"].items(), key=lambda kv: -kv[1])
            )
            lines.append(
                f"  {g['start_s']:>10.3f} {g['seconds']:>9.4f}  {g['label']}"
                + (f"  [{parts}]" if parts else "")
            )
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Per-scope device seconds and span-labelled idle gaps of a capture"
    )
    parser.add_argument("trace_dir", help="the --trace_dir of the captured run")
    parser.add_argument("--gaps", type=int, default=5, help="idle gaps to print")
    parser.add_argument("--json", action="store_true", help="print the report as JSON")
    args = parser.parse_args(argv)
    report = device_trace_report(args.trace_dir)
    print(json.dumps(report) if args.json else render(report, args.gaps))
    return 0


if __name__ == "__main__":
    sys.exit(main())
