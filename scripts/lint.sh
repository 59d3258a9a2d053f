#!/bin/bash
# graftlint over everything that ships: the package, the drivers and
# the scripts. Strict allowlist mode — an entry that no longer
# suppresses anything must be deleted (or its finding has come back).
# Rule catalog + allowlist format: docs/ANALYSIS.md.
# raft_ncup_tpu/observability/ and raft_ncup_tpu/fleet/ are named
# explicitly (they are also inside the package glob): JGL010 holds the
# telemetry subsystem AND the fleet control plane host-only, and the
# redundant paths keep that scope visible even if the package line is
# ever narrowed.
set -e
cd "$(dirname "$0")/.."
exec python -m raft_ncup_tpu.analysis \
    --strict-allowlist \
    raft_ncup_tpu/ raft_ncup_tpu/observability/ raft_ncup_tpu/fleet/ \
    train.py evaluate.py demo.py serve.py scripts/ \
    "$@"
