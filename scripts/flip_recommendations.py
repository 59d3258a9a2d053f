#!/usr/bin/env python
"""Data-driven kernel-default recommendations from a bench record.

Its input is no longer produced: bench.py went in PR 28, and ROADMAP D4
removes this script with its tests.

Reads one bench.py JSON record (file argument or stdin) and prints which
implementation defaults the measurements support flipping:

- ``ModelConfig.corr_impl`` (raft_ncup_tpu/config.py) — 'volume' vs
  'onthefly' vs 'pallas' (reference hot path: core/corr.py:13-44);
- ``RAFT_NCUP_NCONV_IMPL`` (raft_ncup_tpu/ops/nconv.py) — 'xla' vs the
  fused Pallas NConv kernel.

Defaults only flip on ACCELERATOR data: CPU rows order kernels by how
well they suit a host CPU, not the MXU/VMEM tradeoffs the kernels were
built around (docs/PERF.md: volume beats onthefly on CPU at the small
shape for exactly this reason).
"""

from __future__ import annotations

import json
import sys

MARGIN = 1.03  # >=3% win required to recommend changing a default


def recommend(record: dict) -> list[str]:
    lines = []
    key = str(record.get("baseline_key", ""))
    if key.startswith("cpu") or not key:
        # Kernel defaults never flip on CPU data, but the eval-pipeline
        # row's invariant verdict still matters (a leaking loop is a
        # leaking loop on any backend).
        return [
            "no accelerator measurement in this record "
            f"(baseline_key={key or 'absent'!r}); defaults stay "
            "corr_impl='volume', RAFT_NCUP_NCONV_IMPL='xla' pending TPU data"
        ] + _val_row_lines(record) + _serve_row_lines(record) + _bf16_row_lines(
            record
        ) + _highres_row_lines(record) + _uhd_row_lines(
            record
        ) + _pipeline_lines(record) + _earlyexit_lines(
            record
        ) + _fleet_lines(
            record
        ) + _elasticity_lines(record) + _telemetry_lines(record)

    corr = {"volume": record.get("value")}
    for tag in ("onthefly", "pallas"):
        v = record.get(f"pairs_per_sec_{tag}")
        if v:
            corr[tag] = v
    corr = {k: v for k, v in corr.items() if v}
    if not corr or "volume" not in corr:
        # Without the volume row there is no corr comparison: a
        # watchdog-killed primary attempt can leave only variant rows (or
        # none), and flipping on variant-vs-variant data would change the
        # default with no baseline evidence (ADVICE r5). The nconv section
        # below still runs — its fell-back diagnosis needs no baseline.
        lines.append(
            "corr_impl: no volume baseline in record "
            f"(measured: {sorted(corr) or 'none'}); defaults stay — "
            "rerun bench for the primary row"
        )
    else:
        best = max(corr, key=corr.get)
        if len(corr) < 2:
            lines.append(
                f"corr_impl: only {list(corr)} measured; no comparison possible"
            )
        elif best != "volume" and corr[best] >= MARGIN * corr.get("volume", 0):
            lines.append(
                f"corr_impl: FLIP default 'volume' -> '{best}' "
                f"({corr[best]:.2f} vs {corr['volume']:.2f} pairs/s; "
                "edit raft_ncup_tpu/config.py ModelConfig.corr_impl)"
            )
        else:
            lines.append(
                f"corr_impl: keep 'volume' ({ {k: round(v, 2) for k, v in corr.items()} })"
            )

        if "corr_pallas_levels" in record and "pallas" in corr:
            lines.append(
                f"corr: note — pallas row ran the kernel on "
                f"{record['corr_pallas_levels']} pyramid levels (per-level "
                "VMEM gating; partial dispatch is by design at large shapes)"
            )

    # Invariant counters from the runtime guards (bench.py train-loop row
    # under analysis/guards.py): a pipelined-loop number measured while
    # the sync-free/recompile-free invariant was VIOLATED ranks loops, not
    # kernels — flag it before anyone reads the train_loop_* fields as a
    # clean pipeline measurement. (JGL001/JGL008 audit note: this script
    # itself is pure host-side JSON analytics — no per-sample device
    # pulls to batch here; the eval-side ones are routed through the
    # inference pipeline's one-get-per-window contract.)
    transfers = record.get("train_loop_host_transfers")
    recompiles = record.get("train_loop_recompiles")
    if transfers or recompiles:
        lines.append(
            "train_loop: INVARIANT VIOLATED during the pipelined window "
            f"({transfers or 0} implicit host transfer(s), "
            f"{recompiles or 0} recompile(s)) — the train_loop_* numbers "
            "measure a stalling loop; fix the leak (see docs/ANALYSIS.md) "
            "before comparing pipeline rows"
        )

    lines.extend(_val_row_lines(record))
    lines.extend(_serve_row_lines(record))
    lines.extend(_bf16_row_lines(record))
    lines.extend(_highres_row_lines(record))
    lines.extend(_uhd_row_lines(record))
    lines.extend(_pipeline_lines(record))
    lines.extend(_earlyexit_lines(record))
    lines.extend(_fleet_lines(record))
    lines.extend(_elasticity_lines(record))
    lines.extend(_telemetry_lines(record))

    nc = record.get("pairs_per_sec_nconv_pallas")
    fell_back = record.get("pairs_per_sec_nconv_pallas_FELL_BACK_TO_XLA")
    base = record.get("value")
    calls = str(record.get("nconv_pallas_calls", ""))
    partial = False
    if calls and "/" in calls:
        fused_n, total_n = (int(x) for x in calls.split("/"))
        partial = fused_n < total_n
    if nc and base:
        if partial:
            # A mostly-XLA measurement must not flip the default on a
            # small margin — the number's provenance is mixed.
            lines.append(
                f"nconv: pallas row only PARTIALLY fused ({calls} call "
                f"sites; {nc:.2f} vs {base:.2f} pairs/s) — do NOT flip on "
                "this row; investigate the gated-out call sites first"
            )
        elif nc >= MARGIN * base:
            lines.append(
                f"nconv: FLIP default 'xla' -> 'pallas' ({nc:.2f} vs "
                f"{base:.2f} pairs/s; edit raft_ncup_tpu/ops/nconv.py "
                "RAFT_NCUP_NCONV_IMPL default)"
            )
        else:
            lines.append(
                f"nconv: keep 'xla' (pallas {nc:.2f} vs xla {base:.2f} pairs/s)"
            )
    elif nc:
        lines.append(
            f"nconv: pallas row measured ({nc:.2f} pairs/s) but no volume "
            "baseline to compare against; keep 'xla'"
        )
    elif fell_back:
        lines.append(
            "nconv: pallas row fell back to XLA at this shape "
            f"({fell_back:.2f} pairs/s) — no fused measurement; keep 'xla'"
        )
    else:
        lines.append("nconv: no pallas row measured; keep 'xla'")
    return lines


def _val_row_lines(record: dict) -> list[str]:
    """Eval-pipeline row (bench.py ``val_*`` fields, docs/PERF.md "Eval
    pipeline") — the train-loop policy applied to validation: absent row
    → no lines (older records predate it); nonzero guard counters →
    the numbers measured a leaking loop and are unusable for pipeline
    comparisons; clean row → report the recovered stall."""
    if record.get("val_pairs_per_sec") is None:
        return []
    transfers = record.get("val_loop_host_transfers")
    recompiles = record.get("val_loop_recompiles")
    if transfers or recompiles:
        return [
            "val_loop: INVARIANT VIOLATED during the pipelined eval "
            f"window ({transfers or 0} implicit host transfer(s), "
            f"{recompiles or 0} recompile(s)) — the val_* numbers measure "
            "a leaking loop; fix it (docs/ANALYSIS.md JGL008) before "
            "reading them as a pipeline measurement"
        ]
    stall = record.get("val_stall_ms_per_pair")
    pipe_ms = record.get("val_ms_per_pair")
    if stall is None or pipe_ms is None:
        return [
            "val_loop: row incomplete (no stall bracketing); rerun bench "
            "for the full eval-pipeline row"
        ]
    if stall > 0:
        return [
            f"val_loop: pipelined eval recovers {stall:.1f} ms/pair over "
            f"the per-batch-synced loop ({pipe_ms:.1f} ms/pair pipelined; "
            "invariants clean) — keep the async eval pipeline on"
        ]
    return [
        f"val_loop: no stall recovered on this host ({stall:.1f} ms/pair; "
        "saturated-host or accelerator-absent measurement) — pipeline "
        "stays on for the invariants; judge speed on accelerator rows"
    ]


def _bf16_row_lines(record: dict) -> list[str]:
    """bf16 precision rows (bench.py ``*_bf16`` fields; docs/PRECISION.md)
    — the corr_impl flip discipline applied to the precision default:
    absent row → no lines (older records predate it); any ``*_bf16``
    guard counter nonzero → the numbers measured a leaking/recompiling
    program and are unusable; parity over the recorded budget → never
    flip, regardless of speed; clean + parity met → flip only on
    accelerator data with a >= MARGIN throughput win (CPU emulates bf16
    in software — its ordering says nothing about the MXU)."""
    bf16 = record.get("pairs_per_sec_bf16")
    if bf16 is None and not any("bf16" in k for k in record):
        return []
    # Any bf16-window guard counter, wherever 'bf16' sits in the key:
    # the forward row spells them fwd_bf16_recompiles (prefix), the
    # val/serve/stream rows val_loop_recompiles_bf16 (suffix). These
    # filters run even when the forward row is MISSING — the sub-rows
    # are measured independently (a failed forward row does not stop
    # bench's later bf16 rows), and dirty numbers without an 'unusable'
    # flag are exactly the misread this function exists to prevent.
    dirty = {
        k: v
        for k, v in record.items()
        if "bf16" in k
        and ("recompiles" in k or "host_transfers" in k)
        and v
    }
    if dirty:
        return [
            "bf16: INVARIANT VIOLATED during bf16 window(s) "
            f"({dirty}) — the *_bf16 numbers measure a leaking or "
            "recompiling program; fix the leak (docs/ANALYSIS.md) "
            "before reading them, and do NOT flip the precision default"
        ]
    failed = {
        k: v
        for k, v in record.items()
        if "bf16" in k and "errors" in k and v
    }
    if failed:
        return [
            f"bf16: window(s) ERRORED ({failed}) — the *_bf16 numbers "
            "cover a partial sample; fix the failure and rerun bench "
            "before judging the precision default"
        ]
    if bf16 is None:
        return [
            "bf16: forward row missing (other *_bf16 rows recorded, "
            "invariants clean); rerun bench for the bf16 forward row — "
            "no parity measurement, no flip verdict"
        ]
    parity = record.get("bf16_forward_epe_vs_f32")
    budget = record.get("bf16_epe_budget")
    if parity is None or budget is None:
        return [
            "bf16: row incomplete (no parity measurement); rerun bench "
            "for the bf16 forward row before judging the precision "
            "default"
        ]
    if parity > budget:
        return [
            f"bf16: parity budget EXCEEDED ({parity:.4f} px EPE vs f32, "
            f"budget {budget:.4f}) — do NOT flip the precision default; "
            "investigate the drift (docs/PRECISION.md error-budget "
            "methodology) before trusting bf16 numbers"
        ]
    base = record.get("value")
    key = str(record.get("baseline_key", ""))
    on_accel = bool(key) and not key.startswith("cpu")
    if on_accel and base and bf16 >= MARGIN * base:
        return [
            f"precision: FLIP default 'f32' -> 'bf16_infer' "
            f"({bf16:.2f} vs {base:.2f} pairs/s, parity {parity:.4f} px "
            f"within budget {budget:.4f}, invariants clean; edit "
            "raft_ncup_tpu/config.py ModelConfig.precision — and retest "
            "bf16_train before flipping the training default)"
        ]
    if on_accel:
        return [
            f"bf16: parity within budget ({parity:.4f} px) but no >= "
            f"{MARGIN:.2f}x win ({bf16:.2f} vs {base or 0:.2f} pairs/s); "
            "keep precision 'f32'"
        ]
    return [
        f"bf16: parity within budget ({parity:.4f} px, invariants "
        f"clean) on a CPU row ({bf16:.2f} vs {base or 0:.2f} pairs/s, "
        "bf16 emulated) — no flip from CPU data; rows are staged for "
        "first hardware contact"
    ]


def _highres_row_lines(record: dict) -> list[str]:
    """Spatially-sharded 1080p row (bench.py ``highres_*`` fields;
    docs/SHARDING.md) — the corr_impl flip discipline applied to the
    serving/streaming mesh default: absent row → no lines (older
    records predate it); nonzero guard counters → the numbers measured
    a leaking/recompiling program and are unusable; a clean
    multi-device window with a >= MARGIN win over its own
    single-device comparison, on ACCELERATOR data → flip the
    serve/stream default mesh (CPU emulates the mesh on virtual host
    devices — its ordering says nothing about ICI collectives)."""
    hr = record.get("highres_pairs_per_sec")
    if hr is None:
        return []
    transfers = record.get("highres_host_transfers")
    recompiles = record.get("highres_recompiles")
    if transfers or recompiles:
        return [
            "highres: INVARIANT VIOLATED during the 1080p window(s) "
            f"({transfers or 0} implicit host transfer(s), "
            f"{recompiles or 0} recompile(s)) — the highres_* numbers "
            "measure a leaking or recompiling program; fix the leak "
            "(docs/ANALYSIS.md) before reading them or judging the mesh"
        ]
    devices = record.get("highres_devices") or 1
    mesh = record.get("highres_mesh", "nomesh")
    if devices <= 1:
        return [
            f"highres: single-device row ({hr:.3f} pairs/s at "
            f"{record.get('highres_iters', '?')} iters, invariants "
            "clean) — no mesh to judge; rerun with >1 visible device "
            "(--mesh) for the sharded row"
        ]
    ref = record.get("highres_pairs_per_sec_unsharded")
    if ref is None:
        return [
            f"highres: sharded row clean ({hr:.3f} pairs/s on {mesh}) "
            "but no single-device comparison in the record "
            "(BENCH_HIGHRES_COMPARE=0?); no mesh verdict without it"
        ]
    key = str(record.get("baseline_key", ""))
    on_accel = bool(key) and not key.startswith("cpu")
    if on_accel and ref and hr >= MARGIN * ref:
        return [
            f"highres: FLIP serve/stream default mesh — {mesh} measured "
            f"{hr:.3f} vs {ref:.3f} pairs/s single-device at 1080p "
            "(invariants clean; set ServeConfig.mesh / StreamConfig.mesh "
            "in raft_ncup_tpu/config.py, or --mesh on serve.py)"
        ]
    if on_accel:
        return [
            f"highres: mesh {mesh} shows no >= {MARGIN:.2f}x win at "
            f"1080p ({hr:.3f} vs {ref:.3f} pairs/s single-device); keep "
            "the unsharded default — sharding still buys per-device "
            f"memory ({record.get('highres_analysis_temp_gib', '?')} vs "
            f"{record.get('highres_analysis_temp_gib_unsharded', '?')} "
            "GiB temp)"
        ]
    return [
        f"highres: sharded row clean on CPU-emulated {mesh} "
        f"({hr:.3f} vs {ref:.3f} pairs/s single-device; per-device temp "
        f"{record.get('highres_analysis_temp_gib', '?')} vs "
        f"{record.get('highres_analysis_temp_gib_unsharded', '?')} GiB) "
        "— no mesh flip from CPU data; the row is staged for first "
        "hardware contact"
    ]


def _uhd_row_lines(record: dict) -> list[str]:
    """UHD/4K row (bench.py ``uhd_*`` fields; docs/PERF.md "Banded
    dispatch") — the corr-tier discipline at the shape the banded
    kernel exists for: absent row → no lines (older records predate
    it); dirty-or-missing guard counters → the window is unusable;
    CPU → staged, never a flip (a CPU 4K window runs the XLA fallback
    at reduced iters — it proves servability, not kernel ordering);
    clean accelerator → the corr-tier verdict (which tier carried the
    levels, and whether corr_impl='pallas' is the 4K candidate)."""
    uhd = record.get("uhd_pairs_per_sec")
    if uhd is None:
        return []
    transfers = record.get("uhd_host_transfers")
    recompiles = record.get("uhd_recompiles")
    if transfers or recompiles or transfers is None or recompiles is None:
        return [
            "uhd: INVARIANT VIOLATED (or unrecorded) during the 4K "
            f"window ({transfers if transfers is not None else '?'} "
            "implicit host transfer(s), "
            f"{recompiles if recompiles is not None else '?'} "
            "recompile(s)) — the uhd_* numbers are unusable; fix the "
            "leak (docs/ANALYSIS.md) before reading them"
        ]
    impl = record.get("uhd_corr_impl", "?")
    shape = record.get("uhd_shape", "?")
    knobs = (
        f"row_chunk={record.get('uhd_corr_row_chunk', '?')}, "
        f"query_block={record.get('uhd_corr_query_block', '?')}, "
        f"band_rows={record.get('uhd_corr_band_rows', '?')}"
    )
    key = str(record.get("baseline_key", ""))
    on_accel = bool(key) and not key.startswith("cpu")
    if not on_accel:
        return [
            f"uhd: 4K window clean on CPU ({uhd:.4f} pairs/s at "
            f"{shape}/{record.get('uhd_iters', '?')}it via "
            f"'{impl}'; {knobs}) — proves 4K is servable, says nothing "
            "about kernel ordering; the corr-tier verdict is staged "
            "for first hardware contact"
        ]
    dispatch = record.get("uhd_corr_dispatch") or {}
    if impl == "pallas" and dispatch:
        fb = dispatch.get("fallback", 0)
        if fb:
            return [
                f"uhd: pallas window clean ({uhd:.3f} pairs/s) but "
                f"{fb}/{dispatch.get('levels_total', '?')} pyramid "
                "level(s) still fell back to XLA — tune the band knobs "
                f"({knobs}; RAFT_NCUP_CORR_BAND_ROWS/"
                "RAFT_NCUP_CORR_QUERY_BLOCK) before judging the 4K tier"
            ]
        return [
            f"uhd: 4K corr tier VERDICT — '{impl}' carried every level "
            f"on-kernel (resident {dispatch.get('kernel', 0)} + banded "
            f"{dispatch.get('banded', 0)}; {uhd:.3f} pairs/s, "
            f"invariants clean, {knobs}); corr_impl='pallas' is the 4K "
            "default candidate — compare an onthefly rerun "
            "(BENCH_UHD_CORR=onthefly) before flipping "
            "ModelConfig.corr_impl for UHD serving"
        ]
    return [
        f"uhd: accelerator window clean via '{impl}' ({uhd:.3f} "
        f"pairs/s at {shape}; {knobs}) — rerun with "
        "BENCH_UHD_CORR=pallas for the kernel-tier comparison before "
        "any corr verdict"
    ]


def _pipeline_lines(record: dict) -> list[str]:
    """Iteration-pipeline row (bench.py ``pipeline_*`` fields;
    docs/SHARDING.md "Pipeline axis") — whether the pipe-axis streaming
    schedule earns its mesh: absent row → no lines (older records
    predate it); dirty-or-missing guard counters → the stream is
    unusable; S=1 → the delegation path, nothing to judge; CPU →
    staged, never a flip (virtual pipeline stages share one host — the
    S× claim is unmeasurable, only the invariants and the
    collective-permute fingerprint carry); clean accelerator → the
    pipeline-vs-monolithic verdict at MARGIN."""
    pps = record.get("pipeline_pairs_per_sec")
    if pps is None:
        return []
    transfers = record.get("pipeline_host_transfers")
    recompiles = record.get("pipeline_recompiles")
    if transfers or recompiles or transfers is None or recompiles is None:
        return [
            "pipeline: INVARIANT VIOLATED (or unrecorded) during the "
            "streaming window "
            f"({transfers if transfers is not None else '?'} implicit "
            "host transfer(s), "
            f"{recompiles if recompiles is not None else '?'} "
            "recompile(s)) — the pipeline_* numbers measure a stalling "
            "stream; fix the leak (docs/ANALYSIS.md) before reading them"
        ]
    segs = record.get("pipeline_segments", "?")
    shape = record.get("pipeline_shape", "?")
    perm = record.get("pipeline_collective_permutes")
    if segs == 1:
        return [
            f"pipeline: single-stage record ({pps:.4f} pairs/s at "
            f"{shape} via the monolithic delegation path) — no pipe "
            "mesh on this host; rerun with >1 visible device (or "
            "BENCH_PIPELINE_SEGMENTS) for a pipeline measurement"
        ]
    handoff = (
        f"{perm} collective-permute(s)/tick"
        if perm is not None
        else "handoff fingerprint unrecorded"
    )
    key = str(record.get("baseline_key", ""))
    on_accel = bool(key) and not key.startswith("cpu")
    if not on_accel:
        return [
            f"pipeline: S={segs} stream clean on CPU ({pps:.4f} "
            f"pairs/s at {shape}/"
            f"{record.get('pipeline_iters', '?')}it, "
            f"{record.get('pipeline_micro_batches', '?')} micro-"
            f"batches, {handoff}, invariants clean) — virtual stages "
            "share one host, so this proves schedule correctness, not "
            "throughput; the pipeline-vs-monolithic verdict is staged "
            "for first hardware contact"
        ]
    mono = record.get("pipeline_pairs_per_sec_monolithic")
    if not mono:
        return [
            f"pipeline: S={segs} accelerator stream clean ({pps:.3f} "
            f"pairs/s, {handoff}) but no monolithic comparison window "
            "in the record — rerun without BENCH_PIPELINE_COMPARE=0 "
            "before any verdict"
        ]
    if pps >= MARGIN * mono:
        return [
            f"pipeline: VERDICT — S={segs} streaming beats the "
            f"monolithic scan ({pps:.3f} vs {mono:.3f} pairs/s at "
            f"{shape}; {handoff}; per-segment "
            f"{record.get('pipeline_flops_per_segment', '?')} flops); "
            "adopt the pipe mesh for streaming inference (ServeConfig "
            "mesh=(1,1,S)) and sweep S per ROADMAP item 1's chip-window "
            "checklist"
        ]
    return [
        f"pipeline: keep the monolithic scan — S={segs} streaming "
        f"({pps:.3f} pairs/s) does not clear the monolithic window "
        f"({mono:.3f} pairs/s) by the {MARGIN}x margin; the handoff "
        f"cost ({handoff}) is not yet paying for itself at this "
        "shape/iters"
    ]


def _earlyexit_lines(record: dict) -> list[str]:
    """Early-exit row (bench.py ``earlyexit_*`` fields; docs/PERF.md
    "Early exit") — the one speedup verdict this script WILL issue from
    CPU data: the measured win is a FLOP cut (fewer while_loop trips),
    honest on every backend, unlike kernel ordering or mesh claims.
    Absent row → no lines (older records predate it); dirty-or-missing
    guard counters → the windows are unusable (a recompile means the
    tolerance leaked into shapes; a transfer means convergence was
    inspected on the host); EPE over the pinned budget → never enable,
    regardless of speed; within budget + >= MARGIN throughput win over
    the full-budget twin → recommend enabling the knob."""
    pps = record.get("earlyexit_pairs_per_sec")
    if pps is None:
        return []
    transfers = record.get("earlyexit_host_transfers")
    recompiles = record.get("earlyexit_recompiles")
    if transfers or recompiles or transfers is None or recompiles is None:
        return [
            "earlyexit: INVARIANT VIOLATED (or unrecorded) during the "
            "adaptive-compute window(s) "
            f"({transfers if transfers is not None else '?'} implicit "
            "host transfer(s), "
            f"{recompiles if recompiles is not None else '?'} "
            "recompile(s)) — detection must live in-graph with a closed "
            "executable set; the earlyexit_* numbers are unusable until "
            "the leak is fixed (docs/ANALYSIS.md)"
        ]
    full = record.get("earlyexit_pairs_per_sec_fullbudget")
    epe = record.get("earlyexit_epe_vs_full")
    budget = record.get("earlyexit_epe_budget")
    if not full or epe is None or budget is None:
        return [
            "earlyexit: row incomplete (no full-budget twin or parity "
            "measurement); rerun bench for the full early-exit row "
            "before judging the knob"
        ]
    tol = record.get("earlyexit_tol", "?")
    execd = record.get("earlyexit_iters_executed_mean", "?")
    budgeted = record.get("earlyexit_iters_budgeted", "?")
    if epe > budget:
        return [
            f"earlyexit: quality budget EXCEEDED ({epe:.4f} px EPE vs "
            f"the full-budget twin, budget {budget:.4f}, tol={tol}) — "
            "do NOT enable RAFT_NCUP_EARLYEXIT at this tolerance; "
            "tighten RAFT_NCUP_EARLYEXIT_TOL and rerun bench"
        ]
    if pps >= MARGIN * full:
        return [
            f"earlyexit: VERDICT — enable RAFT_NCUP_EARLYEXIT=1 "
            f"(RAFT_NCUP_EARLYEXIT_TOL={tol}): {pps:.2f} vs {full:.2f} "
            f"pairs/s full-budget at matched quality ({epe:.4f} px EPE "
            f"within {budget:.4f}), executed {execd} of {budgeted} "
            "budgeted iters mean, invariants clean — the FLOP cut is "
            "backend-honest, so this CPU verdict carries"
        ]
    return [
        f"earlyexit: keep the knob off — {pps:.2f} vs {full:.2f} "
        f"pairs/s full-budget misses the {MARGIN}x margin (parity "
        f"{epe:.4f} px within {budget:.4f}; executed {execd} of "
        f"{budgeted} budgeted iters mean); per-call overhead is "
        "swallowing the FLOP cut at this shape mix"
    ]


def _telemetry_lines(record: dict) -> list[str]:
    """Telemetry snapshot consistency (bench.py serve/stream rows;
    docs/OBSERVABILITY.md) — absent snapshot fields → no lines (older
    records predate them); a window whose sanctioned drain-pull counter
    drifts from its dispatched-batch counter → flagged INCONSISTENT
    (the two are independent measurements of the same thing: one
    AsyncDrain pull per dispatched batch — drift means results were
    delivered outside the sanctioned path, or dropped); equal → a
    one-line consistency confirmation. The measured observer overhead
    is also judged against its 3%-of-p50 budget when recorded."""
    lines = []
    for prefix in ("serve", "stream"):
        gets = record.get(f"{prefix}_sanctioned_gets")
        batches = record.get(f"{prefix}_batches")
        if gets is None or batches is None:
            continue  # no telemetry snapshot in this record
        if gets != batches:
            lines.append(
                f"telemetry: {prefix} snapshot INCONSISTENT — "
                f"{gets} sanctioned drain pull(s) vs {batches} dispatched "
                "batch(es) in the window; every batch's results must "
                "ride exactly one sanctioned AsyncDrain device_get, so "
                f"the drift means the {prefix}_* numbers cover deliveries "
                "outside the sanctioned path (or dropped batches) — "
                "explain it (docs/OBSERVABILITY.md) before reading them"
            )
        else:
            lines.append(
                f"telemetry: {prefix} snapshot consistent "
                f"({gets} sanctioned pull(s) = {batches} batch(es))"
            )
    overhead = record.get("serve_telemetry_overhead_pct")
    if overhead is not None and overhead > 3.0:
        lines.append(
            f"telemetry: serve tracing overhead {overhead:.1f}% of p50 "
            "EXCEEDS the 3% budget (docs/OBSERVABILITY.md methodology) — "
            "profile the tracer hot path before keeping tracing-on "
            "defaults"
        )
    lines.extend(_slo_lines(record))
    return lines


def _slo_lines(record: dict) -> list[str]:
    """Health/SLO verdict block (bench.py serve/stream rows;
    docs/OBSERVABILITY.md "SLO burn rate") — absent block → no lines
    (older records predate it); a window whose health ended DEGRADED
    (or worse) or that paged an SLO → flagged: the latencies were
    measured while the budget controller was coarsening responses, so
    they describe a degraded service, not the steady state every other
    verdict assumes; clean → one confirmation line naming the verdict
    count."""
    lines = []
    for prefix in ("serve", "stream"):
        health = record.get(f"{prefix}_health")
        verdicts = record.get(f"{prefix}_slo")
        if health is None and verdicts is None:
            continue  # no health/SLO block in this record
        pages = record.get(f"{prefix}_slo_pages") or 0
        paging = sorted(
            name for name, v in (verdicts or {}).items() if v.get("page")
        )
        if health not in (None, "ready") or pages or paging:
            detail = []
            if health not in (None, "ready"):
                detail.append(f"health={health}")
            if pages:
                detail.append(f"{pages} page(s)")
            if paging:
                detail.append("paging: " + ", ".join(paging))
            lines.append(
                f"slo: {prefix} window DEGRADED ({'; '.join(detail)}) — "
                f"the {prefix}_* latencies include coarsened (degraded-"
                "budget) responses; fix the burn or lower the load and "
                "rerun bench before reading them as steady state"
            )
        else:
            lines.append(
                f"slo: {prefix} window clean (health=ready, 0 pages "
                f"over {len(verdicts or {})} declared SLO(s))"
            )
    return lines


def _fleet_lines(record: dict) -> list[str]:
    """Fleet row (bench.py ``fleet_*`` fields; docs/FLEET.md) — the
    serve-row policy applied per replica: absent row → no lines (older
    records predate the fleet tier); any replica's guard counters
    nonzero → the whole row is unusable (one leaking replica poisons
    the fleet percentiles); sheds/errors/failovers or a drain-contract
    violation → the row measured robustness machinery, not service;
    clean → the router-hop verdict against the single-replica serve
    row, with per-replica occupancy."""
    if record.get("fleet_pairs_per_sec") is None:
        return []
    recompiles = record.get("fleet_replica_recompiles") or []
    transfers = record.get("fleet_replica_host_transfers") or []
    dirty = [
        i for i, (r, t) in enumerate(zip(recompiles, transfers))
        if (r is None or r) or (t is None or t)
    ]
    if dirty:
        return [
            "fleet: INVARIANT VIOLATED on replica(s) "
            f"{dirty} (per-replica recompiles {recompiles}, implicit "
            f"host transfers {transfers}; None = report missing) — the "
            "fleet_* latencies include a leaking or recompiling "
            "replica; fix it (docs/FLEET.md) before reading them"
        ]
    shed = record.get("fleet_shed") or 0
    errors = record.get("fleet_errors") or 0
    failovers = record.get("fleet_failovers") or 0
    deaths = record.get("fleet_deaths") or 0
    violations = record.get("fleet_contract_violations") or []
    # Any response that is not ok shrank the latency sample: timeouts/
    # rejections count against steady state exactly like sheds, and a
    # row whose ok count is short of its request count is lossy even if
    # every per-status field reads 0 (belt and suspenders).
    timeouts = record.get("fleet_timeouts") or 0
    rejected = record.get("fleet_rejected") or 0
    n_req = record.get("fleet_requests")
    n_ok = record.get("fleet_ok")
    lossy = (
        n_req is not None and n_ok is not None and n_ok < n_req
    )
    if (shed or errors or failovers or deaths or violations
            or timeouts or rejected or lossy):
        return [
            f"fleet: window NOT steady state ({shed} shed, {errors} "
            f"error(s), {timeouts} timeout(s), {rejected} rejected, "
            f"{failovers} failover(s), {deaths} replica "
            f"death(s), {len(violations)} drain-contract violation(s); "
            f"ok {n_ok}/{n_req}) "
            "— the fleet_* numbers measured the robustness machinery, "
            "not service; rerun bench on a healthy fleet"
        ]
    p50 = record.get("fleet_p50_ms")
    p99 = record.get("fleet_p99_ms")
    if p50 is None or p99 is None:
        return [
            "fleet: row incomplete (no latency percentiles); rerun "
            "bench for the full fleet row"
        ]
    serve_p50 = record.get("serve_p50_ms")
    hop = (
        f"; router hop vs single-replica serve row: "
        f"{p50 - serve_p50:+.1f} ms of p50"
        if serve_p50 is not None else
        "; no serve row in this record to compare the router hop against"
    )
    occ = record.get("fleet_per_replica_completed")
    lines = [
        f"fleet: steady state {record['fleet_pairs_per_sec']:.2f} "
        f"pairs/s over {record.get('fleet_replicas', '?')} replicas, "
        f"p50 {p50:.1f} ms / p99 {p99:.1f} ms "
        f"(per-replica guard counters all 0; occupancy {occ}){hop}"
    ]
    # Fleet telemetry overhead (bench's on/off window over the SAME
    # warm fleet, router + replica hubs toggled over the wire): the
    # serve row's 3% observer budget applied at fleet granularity.
    overhead = record.get("fleet_telemetry_overhead_pct")
    if overhead is not None:
        if overhead > 3.0:
            lines.append(
                f"fleet telemetry: tracing overhead {overhead:.1f}% of "
                "p50 EXCEEDS the 3% budget "
                f"(p50 {p50:.1f} ms on vs "
                f"{record.get('fleet_p50_ms_notelemetry')} ms off) — "
                "profile the fleet producer paths before trusting the "
                "fleet latencies (docs/OBSERVABILITY.md)"
            )
        else:
            lines.append(
                f"fleet telemetry: measured overhead {overhead:.1f}% of "
                "p50 (within the 3% budget)"
            )
    return lines


def _elasticity_lines(record: dict) -> list[str]:
    """Elasticity row (bench.py ``elasticity_*`` fields; docs/FLEET.md
    "Elasticity bench") — the fleet-row policy INVERTED: that row must
    measure service (any shed disqualifies it), this row must measure
    the machinery. Absent row → no lines (older records predate the
    autoscaler); any in-flight loss, drain-contract violation, or open
    breaker → the cycle is UNSAFE and nothing else about the row
    matters; a leaking replica → the latencies are unusable; otherwise
    the verdict is whether the elastic cycle CLOSED — the load step
    forced a scale-up, the capacity reached READY, and the post-burst
    calm gave it back — with the warmup-window sheds carrying
    ETA-floored (not treadmill-default) retry hints."""
    n_req = record.get("elasticity_requests")
    if n_req is None:
        return []
    losses = record.get("elasticity_losses") or 0
    violations = record.get("elasticity_contract_violations") or []
    breaker = record.get("elasticity_breaker_open")
    if losses or violations or breaker:
        detail = []
        if losses:
            detail.append(f"{losses} lost in-flight response(s)")
        if violations:
            detail.append(
                f"{len(violations)} drain-contract violation(s): "
                f"{violations}"
            )
        if breaker:
            detail.append(
                "autoscaler breaker OPEN (consecutive failed scale-ups)"
            )
        return [
            f"elasticity: cycle UNSAFE ({'; '.join(detail)}) — elastic "
            "scaling may NOT be enabled on this build; fix the loss "
            "path (docs/FLEET.md drain contract) and rerun bench"
        ]
    recompiles = record.get("elasticity_replica_recompiles") or []
    transfers = record.get("elasticity_replica_host_transfers") or []
    dirty = [
        i for i, (r, t) in enumerate(zip(recompiles, transfers))
        if (r is None or r) or (t is None or t)
    ]
    if dirty:
        return [
            "elasticity: INVARIANT VIOLATED on serving replica(s) "
            f"{dirty} (recompiles {recompiles}, implicit host transfers "
            f"{transfers}; None = report missing) — the elasticity "
            "latencies include a leaking or recompiling replica; fix it "
            "before reading them"
        ]
    ups = record.get("elasticity_scale_ups") or 0
    ups_done = record.get("elasticity_scale_ups_completed") or 0
    downs = record.get("elasticity_scale_downs") or 0
    shed = record.get("elasticity_shed") or 0
    floored = record.get("elasticity_shed_eta_floored") or 0
    ttr = record.get("elasticity_time_to_ready_s")
    lines = []
    if not ups:
        lines.append(
            f"elasticity: step never pressured the fleet (0 scale-ups "
            f"over {n_req} requests, {shed} shed) — no elasticity "
            "verdict; raise BENCH_ELASTICITY_HIGH or check the "
            "calibrated interval before reading the row"
        )
    elif ups_done < ups:
        lines.append(
            f"elasticity: cycle OPEN — {ups - ups_done} of {ups} "
            "scale-up(s) never reached READY in the window "
            f"({record.get('elasticity_failed_scale_ups') or 0} failed) "
            "— raise BENCH_ELASTICITY_GRACE_S (spawn compile may exceed "
            "the settle window on CPU) and rerun before judging"
        )
    elif downs < ups_done:
        lines.append(
            f"elasticity: capacity never given back ({ups_done} "
            f"scale-up(s) READY after {ttr}s but only {downs} "
            "scale-down(s)) — the cooldown phase or settle window is "
            "too short for the anti-flap bounds; rerun before judging"
        )
    else:
        lines.append(
            "elasticity: cycle CLOSED — the load step scaled "
            f"{ups} up (READY in {ttr}s measured) and the calm gave "
            f"{downs} back with 0 lost in-flight responses "
            f"(ok {record.get('elasticity_ok')}/{n_req}, {shed} honest "
            f"shed(s), p50 {record.get('elasticity_p50_ms')} ms / p99 "
            f"{record.get('elasticity_p99_ms')} ms); elastic scaling "
            "holds its zero-loss contract on this build"
        )
    if shed and not floored:
        lines.append(
            f"elasticity: backpressure DISHONEST — {shed} shed(s) "
            "during the window and none carried a retry hint above the "
            "default floor; while capacity warms, sheds must quote the "
            "time-to-READY estimate (FleetRouter.set_scale_eta), not "
            "the re-shed treadmill"
        )
    return lines


def _serve_row_lines(record: dict) -> list[str]:
    """Serving row (bench.py ``serve_*`` fields; docs/SERVING.md) — the
    val-row policy applied to the serving tier: absent row → no lines
    (older records predate it); nonzero guard counters → the latencies
    measured a leaking/recompiling server and are unusable; a window
    that shed or timed out → it measured backpressure, not service;
    clean → the steady-state latency verdict the SLO reads."""
    if record.get("serve_pairs_per_sec") is None:
        return []
    transfers = record.get("serve_host_transfers")
    recompiles = record.get("serve_recompiles")
    if transfers or recompiles:
        return [
            "serve: INVARIANT VIOLATED during the serving window "
            f"({transfers or 0} implicit host transfer(s), "
            f"{recompiles or 0} recompile(s)) — the serve_* latencies "
            "measure a leaking or recompiling server; fix it "
            "(docs/SERVING.md, docs/ANALYSIS.md) before reading them "
            "as a service-time measurement"
        ]
    shed = record.get("serve_shed") or 0
    timeouts = record.get("serve_timeouts") or 0
    errors = record.get("serve_errors") or 0
    drops = record.get("serve_budget_drops") or 0
    if shed or timeouts:
        return [
            f"serve: window OVERLOADED ({shed} shed, {timeouts} "
            "timeout(s)) — the serve_* numbers measured backpressure, "
            "not steady-state service; lower the arrival rate or raise "
            "capacity and rerun bench"
        ]
    if errors:
        return [
            f"serve: window ERRORED ({errors} request(s) failed "
            "server-side) — the percentiles cover a partial sample; "
            "fix the failure and rerun bench before reading them"
        ]
    p50 = record.get("serve_p50_ms")
    p99 = record.get("serve_p99_ms")
    if p50 is None or p99 is None:
        return [
            "serve: row incomplete (no latency percentiles); rerun "
            "bench for the full serving row"
        ]
    degr = (
        f"; budget degraded {drops}x during the window (arrival rate "
        "sits near capacity — p99 includes coarser-flow responses)"
        if drops else "; budget never degraded (full-quality responses)"
    )
    n_ok = record.get("serve_ok", record.get("serve_requests", "?"))
    return [
        f"serve: steady state {record['serve_pairs_per_sec']:.2f} "
        f"pairs/s, p50 {p50:.1f} ms / p99 {p99:.1f} ms at "
        f"{record.get('serve_iters', '?')} iters over "
        f"{n_ok} requests "
        f"(invariants clean){degr}"
    ]


def main() -> None:
    src = open(sys.argv[1]) if len(sys.argv) > 1 else sys.stdin
    text = src.read().strip()
    if not text:
        print(
            "flip_recommendations: no input (bench produced no record?)",
            file=sys.stderr,
        )
        raise SystemExit(1)
    # Accept either a bare record or bench stdout whose LAST line is JSON.
    try:
        record = json.loads(text.splitlines()[-1])
    except ValueError as e:
        print(
            f"flip_recommendations: last input line is not JSON ({e})",
            file=sys.stderr,
        )
        raise SystemExit(1)
    print("kernel-default recommendations:")
    for line in recommend(record):
        print("  - " + line)


if __name__ == "__main__":
    main()
