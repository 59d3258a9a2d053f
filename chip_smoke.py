#!/usr/bin/env python
"""First contact: drive the flagship serve / eval / train path on the chip.

One process, jax imported once, no child that needs the chip. Synthetic
inputs from a fixed seed, weights from ``model.init`` (the chip machine
has no network and no datasets). Every phase goes through the code a
user calls (``ShapeCachedForward``, ``serve.main``, ``train.main``),
prints one JSON line, and raises on the first failed check — nothing
here catches a failure to let the run continue, and nothing forces a
platform: without a TPU the device phase fails and no result is printed.

    python chip_smoke.py            # one chip: device, eval, server,
                                    # warmstart, trainer, kernels
    python chip_smoke.py --chips 4  # ONLY the (data=2, spatial=2) mesh
                                    # train step and its one-device twin

The LAST stdout line is the result object the driver reads:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The sizes are arguments of the phase functions so that
tests/test_chip_smoke.py can rehearse the control flow at a toy size on
the CPU; ``main()`` always runs the real ones.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import re
import sys
import time

MODEL = "raft_nc_dbl"
EVAL_HW = (436, 1024)  # Sintel frame; InputPadder pads it to 440x1024
EVAL_ITERS = 32
SERVE_HW = (440, 1024)
HD_HW = (1080, 1920)  # 1080p: a multiple of 8, InputPadder adds nothing
TRAIN_HW = (368, 768)  # scripts/train_raft_nc_sintel.sh crop
TRAIN_ITERS = 12
EPE_BUDGET_PX = 0.5  # docs/PRECISION.md: the repo's own parity budget
GIB = 2**30

# Sintel train step (f32, 368x768, 12 iters) compiled ahead of time for a
# described v5e chip in the CPU sandbox (on-chip-measurement guide §2);
# bytes one step asks beyond its arguments, per batch size (my AOT
# compiles, PR 21). The curve is not linear: XLA rematerialises only
# until the program just fits, so batch 4 and 6 sit at the 16 GB edge.
TRAIN_STEP_TEMP_GIB = {6: 16.1, 4: 15.7, 2: 6.2}
TRAIN_HEADROOM_GIB = 1.0


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


@contextlib.contextmanager
def phase(name: str, meter, device=None):
    """Time one phase and print its JSON line when it ends without an
    exception. Yields the dict of facts the body fills; with ``device``
    its peak memory so far is added. ``meter``: the program's compile
    listener (``utils/profiling.compile_meter()``)."""
    facts: dict = {}
    t0 = time.monotonic()
    c0, s0, h0, m0 = meter.snapshot()
    yield facts
    c1, s1, h1, m1 = meter.snapshot()
    if device is not None:
        facts["peak_hbm_gib"] = peak_hbm_gib(device)
    emit({
        "phase": name,
        "wall_s": round(time.monotonic() - t0, 2),
        "compile_s": round(s1 - s0, 2),
        "compiles": c1 - c0,
        "cache_hits": h1 - h0,
        "cache_misses": m1 - m0,
        **facts,
    })


def device_record(devices) -> dict:
    d = devices[0]
    return {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices),
    }


def final_line(device: dict) -> str:
    return json.dumps({"ok": True, "device": device})


def peak_hbm_gib(device) -> float | None:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else round(peak / GIB, 3)


def _synthetic_pair(seed: int, hw: tuple[int, int]):
    import numpy as np

    rng = np.random.default_rng(seed)
    shape = (1, hw[0], hw[1], 3)
    return (
        rng.uniform(0.0, 255.0, shape).astype(np.float32),
        rng.uniform(0.0, 255.0, shape).astype(np.float32),
    )


def _padded_pair(seed: int, hw: tuple[int, int]):
    from raft_ncup_tpu.ops import InputPadder

    img1, img2 = _synthetic_pair(seed, hw)
    padder = InputPadder(img1.shape)
    return padder, *padder.pad(img1, img2)


def _flagship(corr_impl: str):
    from raft_ncup_tpu.config import flagship_config
    from raft_ncup_tpu.models.raft import RAFT

    cfg = flagship_config(dataset="sintel", corr_impl=corr_impl)
    return RAFT(cfg), cfg


def _checked_flow(name: str, padder, up, hw):
    """Unpad a device flow field, pull it, check shape and finiteness."""
    import jax
    import numpy as np

    flow = np.asarray(jax.device_get(padder.unpad(up)))
    check(
        flow.shape == (1, hw[0], hw[1], 2),
        f"{name}: flow shape {flow.shape} != {(1, hw[0], hw[1], 2)}",
    )
    check(bool(np.isfinite(flow).all()), f"{name}: non-finite flow")
    return flow


def _mean_epe(a, b) -> float:
    import numpy as np

    return float(np.sqrt(((a - b) ** 2).sum(-1)).mean())


# ------------------------------------------------------------------ phases


def phase_device(devices, n_chips: int) -> dict:
    """Phase 1: the device is a TPU, there are enough of them, and the
    peak table knows the chip (so ``mfu`` cannot be silently null)."""
    from raft_ncup_tpu.inference.costs import peak_flops

    rec = device_record(devices)
    check(
        rec["platform"] == "tpu",
        f"no TPU: jax.devices()[0].platform == {rec['platform']!r}",
    )
    check(
        rec["count"] >= n_chips,
        f"--chips {n_chips} needs {n_chips} devices, jax found "
        f"{rec['count']}",
    )
    peak = peak_flops(rec["platform"], rec["kind"])  # raises when unknown
    check(bool(peak), f"peak_flops({rec['kind']!r}) is null")
    return {**rec, "peak_flops": peak}


def phase_eval(seed: int, hw=EVAL_HW, iters=EVAL_ITERS):
    """Phase 2: the test-mode forward evaluate.py runs (InputPadder +
    ShapeCachedForward), recompile-free at a repeated shape, and the
    ``volume`` and ``onthefly`` correlation paths cross-checked against
    each other on the device. Returns ``(facts, variables, volume flow)``
    — the kernel phase compares against the same weights and flow."""
    import numpy as np

    from evaluate import load_variables
    from raft_ncup_tpu.analysis.guards import max_recompiles
    from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
    from raft_ncup_tpu.ops import nconv

    padder, p1, p2 = _padded_pair(seed, hw)
    flows = {}
    variables = None
    for impl in ("volume", "onthefly"):
        model, cfg = _flagship(impl)
        if variables is None:
            variables = load_variables(model, cfg, None)
        fwd = ShapeCachedForward(model, variables)
        nconv.reset_dispatch_counts()
        _, up = fwd.forward_device(p1, p2, iters)
        if impl == "volume":
            # The one trace of the forward: every NCUP layer a tap sum on
            # the vector units, none an MXU convolution (the weights net's
            # convolutions are flax layers and never reach nconv2d).
            engines = nconv.dispatch_counts()
            check(
                engines["taps"] > 0 and engines["mxu"] == 0,
                f"NCUP layers by engine {engines}: one is not a tap sum",
            )
            with max_recompiles(0):
                _, up = fwd.forward_device(p1, p2, iters)
        flows[impl] = _checked_flow(impl, padder, up, hw)
    epe = _mean_epe(flows["volume"], flows["onthefly"])
    check(
        epe < EPE_BUDGET_PX,
        f"volume vs onthefly mean EPE {epe} px >= {EPE_BUDGET_PX} px",
    )
    facts = {
        "padded_shape": list(p1.shape),
        "iters": iters,
        "flow_abs_mean_px": float(np.abs(flows["volume"]).mean()),
        "epe_volume_vs_onthefly_px": epe,
        "nconv_engines": engines,
    }
    return facts, variables, flows["volume"]


def _run_cli(main, argv: list[str]) -> tuple[int, dict]:
    """Call an entry point's ``main(argv)`` in-process, capture its
    stdout and return (return code, the JSON report on its last line)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    lines = [l for l in buf.getvalue().splitlines() if l.strip()]
    check(bool(lines), f"{main.__module__}.main printed no report")
    return rc, json.loads(lines[-1])


def phase_server(
    seed: int, out_dir: str, hw=SERVE_HW, num_requests: int = 8,
    iter_levels=(12, 8), batch_sizes=(1, 2), n_streams: int = 2,
    frames_per_stream: int = 4, stream_iters: int = 12, extra=(),
) -> dict:
    """Phase 3: ``serve.main`` in-process — the request server, then the
    streaming engine. serve.main returns 0 whenever it was not
    interrupted, even with errors, so the verdict is read off the report."""
    import serve

    common = [
        "--model", MODEL, "--size", str(hw[0]), str(hw[1]),
        "--seed", str(seed), "--report",
        "--flight_dir", os.path.join(out_dir, "flight"), *extra,
    ]
    csv = lambda xs: ",".join(str(x) for x in xs)  # noqa: E731

    def check_report(name: str, rc: int, rep: dict, want: dict, warm: int):
        check(rc == 0, f"serve.main ({name}) returned {rc}")
        for key, value in want.items():
            check(
                rep[key] == value,
                f"{name} report {key}={rep[key]}, want {value}",
            )
        compiles = rep["executables"]["compiles"]
        check(
            compiles == warm,
            f"{name} compiled {compiles} executables, warm-up alone is "
            f"{warm}: recompiles after warm-up",
        )

    rc, rep = _run_cli(serve.main, common + [
        "--num_requests", str(num_requests),
        "--iter_levels", csv(iter_levels),
        "--serve_batch_sizes", csv(batch_sizes),
        "--queue_capacity", str(2 * num_requests),
    ])
    check_report(
        "serve", rc, rep,
        {"completed": num_requests, "errors": 0, "shed": 0, "rejected": 0,
         "timeouts": 0},
        warm=len(iter_levels) * len(batch_sizes),
    )
    facts = {
        "serve_completed": rep["completed"],
        "serve_executables": rep["executables"],
        "serve_wall_s": rep["serve_wall_s"],
        "serve_p50_ms": rep["serve_p50_ms"],
        "serve_p99_ms": rep["serve_p99_ms"],
    }

    rc, rep = _run_cli(serve.main, common + [
        "--stream", "--n_streams", str(n_streams),
        "--frames_per_stream", str(frames_per_stream),
        "--stream_iters", str(stream_iters),
        "--stream_batch_sizes", csv(batch_sizes),
    ])
    check_report(
        "stream", rc, rep,
        {"completed": n_streams * frames_per_stream, "errors": 0,
         "resets": 0, "shed_frames": 0},
        warm=len(batch_sizes),
    )
    facts.update({
        "stream_completed": rep["completed"],
        "stream_executables": rep["executables"],
        "stream_wall_s": rep["stream_wall_s"],
        "stream_p50_ms": rep["stream_p50_ms"],
    })
    return facts


def phase_warmstart(
    seed: int, variables, lr_hw=(55, 128), hw=EVAL_HW, iters=EVAL_ITERS,
) -> dict:
    """Phase 3b: the warm start's two halves on the device. (1) The
    in-graph splat (``forward_interpolate_batch``: an all-pairs distance
    argmin and a gather by its result) at the deployment's 1/8 grid,
    against the host k-d tree version, on a dense flow and on one with
    landings that leave the image: equal cell for cell, but for near
    ties (the host sums its landing points in float64). (2) Through a
    ``StreamEngine``, the same pair answered warm (second pair of a
    stream) and cold (first pair of another), in one batch: the warm
    answer differs, so the slot table's state reached the forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_ncup_tpu.config import StreamConfig
    from raft_ncup_tpu.observability import Telemetry
    from raft_ncup_tpu.ops.warmstart import (
        forward_interpolate,
        forward_interpolate_batch,
    )
    from raft_ncup_tpu.streaming import StreamEngine

    rng = np.random.default_rng(seed)
    h8, w8 = lr_hw
    flows = np.stack([
        rng.normal(0.0, 1.5, (h8, w8, 2)),  # dense: nearly all land inside
        rng.normal(0.0, 1.5, (h8, w8, 2)) * np.linspace(0.2, 40.0, w8)[None, :, None],
        rng.normal(0.0, 80.0, (h8, w8, 2)),  # few survivors fill the grid
    ]).astype(np.float32)
    on_device = np.asarray(jax.device_get(
        jax.jit(forward_interpolate_batch)(jnp.asarray(flows))
    ))
    on_host = np.stack([forward_interpolate(f) for f in flows])
    differ = [int((a != b).any(-1).sum()) for a, b in zip(on_device, on_host)]
    check(
        max(differ) <= 1e-3 * h8 * w8,
        f"splat on the device differs from the host's in {differ} of "
        f"{h8 * w8} cells a row: argmin or gather is wrong here",
    )

    model, _ = _flagship("volume")
    frames = [
        rng.uniform(0.0, 255.0, (hw[0], hw[1], 3)).astype(np.uint8)
        for _ in range(3)
    ]
    with StreamEngine(
        model, variables,
        StreamConfig(capacity=2, frame_hw=hw, iters=iters, batch_sizes=(2,)),
        telemetry=Telemetry(),
    ) as engine:
        first = engine.submit("a", frames[0], frames[1]).result(1800)
        warm_h = engine.submit("a", frames[1], frames[2])
        cold_h = engine.submit("b", frames[1], frames[2])
        warm, cold = warm_h.result(1800), cold_h.result(1800)
        counters = engine.report()["counters"]
    for name, resp in (("first", first), ("warm", warm), ("cold", cold)):
        check(
            resp.ok and bool(np.isfinite(resp.flow).all()),
            f"stream answer {name!r}: {resp.status} {resp.detail}",
        )
    gap = _mean_epe(warm.flow, cold.flow)
    check(
        gap > 1e-3,
        f"the warm answer equals the cold one of the same pair ({gap} px): "
        "the slot table's flow did not reach the forward",
    )
    check(
        counters["stream_frames_cold_start_total"] == 2,
        f"cold starts {counters['stream_frames_cold_start_total']}, want 2",
    )
    return {
        "splat_grid": [h8, w8],
        "splat_cells_differing_from_host": differ,
        "warm_vs_cold_mean_px": gap,
        "stream_cold_starts": counters["stream_frames_cold_start_total"],
    }


def pick_train_batch(hbm_gib: float) -> tuple[int, str]:
    """Largest reference-compatible batch whose AOT ``temp_size`` leaves
    TRAIN_HEADROOM_GIB of the chip's memory free."""
    for batch in sorted(TRAIN_STEP_TEMP_GIB, reverse=True):
        if TRAIN_STEP_TEMP_GIB[batch] + TRAIN_HEADROOM_GIB <= hbm_gib:
            break
    else:
        raise SmokeFailure(f"no train batch fits {hbm_gib} GiB")
    why = ", ".join(
        f"batch {b}: {t} GiB" for b, t in sorted(TRAIN_STEP_TEMP_GIB.items())
    )
    return batch, (
        f"AOT temp_size per batch ({why}); largest leaving "
        f">= {TRAIN_HEADROOM_GIB} GiB of {hbm_gib:.2f} GiB"
    )


_LOSS_RE = re.compile(r"\bloss (\S+)")


def phase_trainer(
    out_dir: str, batch: int, hw=TRAIN_HW, iters=TRAIN_ITERS,
    steps: int = 3, resume_to: int = 5, precision: str | None = None,
    name: str = "smoke", extra=(),
) -> dict:
    """Phase 4: ``train.main`` for ``steps`` steps and a checkpoint, then
    a second ``train.main`` that restores it and runs on to
    ``resume_to``. Loss finite at every step, step counter right.

    Both runs are given ``--num_steps resume_to`` and the first is ended
    after ``steps`` by the trainer's own preemption path (``--chaos
    sigterm@steps``: one atomic checkpoint, exit EXIT_PREEMPTED). The
    learning-rate schedule bakes ``num_steps`` into the optimizer, so a
    3-step run and a 5-step run are two programs and two multi-minute
    compiles on the chip — save, die, resume is one program, and it is
    the cycle a training job really goes through."""
    import shutil

    import train
    from raft_ncup_tpu.resilience import EXIT_PREEMPTED
    from raft_ncup_tpu.training.checkpoint import CheckpointManager

    ckpt_dir = os.path.join(out_dir, "ckpt")
    run_dir = os.path.join(ckpt_dir, name)
    argv = [
        "--name", name, "--stage", "sintel", "--model", MODEL,
        "--image_size", str(hw[0]), str(hw[1]), "--iters", str(iters),
        "--batch_size", str(batch), "--sum_freq", "1", "--synthetic_ok",
        "--checkpoint_dir", ckpt_dir, "--num_steps", str(resume_to), *extra,
    ]
    if precision:
        argv += ["--precision", precision]

    def latest() -> int | None:
        mgr = CheckpointManager(run_dir)
        try:
            return mgr.latest_step
        finally:
            mgr.close()

    with contextlib.redirect_stdout(sys.stderr):
        rc = train.main(argv + ["--chaos", f"sigterm@{steps}"])
    check(rc == EXIT_PREEMPTED, f"train.main returned {rc}")
    check(latest() == steps, f"checkpoint step {latest()} != {steps}")
    with contextlib.redirect_stdout(sys.stderr):
        rc = train.main(argv + ["--restore_ckpt", run_dir])
    check(rc == 0, f"train.main (resume) returned {rc}")
    check(
        latest() == resume_to,
        f"resumed step counter {latest()} != {resume_to}",
    )
    with open(os.path.join(run_dir, "log.txt")) as f:
        log = f.read()
    check(f"restored step {steps} from" in log, "resume did not restore")
    losses = [float(x) for x in _LOSS_RE.findall(log)]
    check(
        len(losses) == resume_to,
        f"log.txt holds {len(losses)} loss lines, want {resume_to}",
    )
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    # A flagship train state is ~35 MB a step: keep the log, not the
    # payloads (what comes back from the chip machine is capped).
    shutil.copy(os.path.join(run_dir, "log.txt"),
                os.path.join(out_dir, f"{name}_log.txt"))
    shutil.rmtree(ckpt_dir)
    return {
        "batch": batch, "precision": precision or "f32", "losses": losses,
        "resumed_step": resume_to,
    }


def _pallas_against_volume(
    seed: int, variables, hw, iters: int, flow_volume=None
) -> tuple[dict, float]:
    """``corr_impl="pallas"`` compiled by Mosaic and run at ``hw``, batch
    1, against the ``volume`` flow of the same weights and frames (made
    here when the caller has none). Dispatch counts and the compiled
    text are read so that the check cannot pass on XLA. Returns
    ``(dispatch tally, mean EPE in px)``."""
    import jax

    from raft_ncup_tpu.ops import corr_pallas

    padder, p1, p2 = _padded_pair(seed, hw)

    def forward(impl):
        model, _ = _flagship(impl)
        return jax.jit(
            lambda v, a, b: model.apply(v, a, b, iters=iters, test_mode=True)[1]
        )

    if flow_volume is None:
        flow_volume = _checked_flow(
            "volume", padder, forward("volume")(variables, p1, p2), hw
        )
    corr_pallas.reset_dispatch_counts()
    compiled = forward("pallas").lower(variables, p1, p2).compile()
    tiers = corr_pallas.dispatch_counts()
    check(
        tiers["fallback"] == 0 and tiers["levels_total"] > 0,
        f"corr_impl=pallas at {hw} dispatch {tiers}: a level fell back to XLA",
    )
    check(
        "tpu_custom_call" in compiled.as_text(),
        f"corr_impl=pallas at {hw}: no tpu_custom_call in the compiled text",
    )
    flow = _checked_flow("pallas", padder, compiled(variables, p1, p2), hw)
    corr_epe = _mean_epe(flow_volume, flow)
    check(
        corr_epe < EPE_BUDGET_PX,
        f"volume vs pallas at {hw} mean EPE {corr_epe} px >= {EPE_BUDGET_PX} px",
    )
    return tiers, corr_epe


def phase_kernels(seed: int, variables, flow_volume, hw=EVAL_HW,
                  iters=EVAL_ITERS, nconv_hw=TRAIN_HW, hd_hw=HD_HW) -> dict:
    """Phase 5: both Pallas kernels compiled by Mosaic and checked
    against their XLA twins on the device: the correlation kernel against
    the eval phase's ``volume`` flow (same weights and frames), then
    again at ``hd_hw``, the shape of the benchmark's ``eval_1080p_nc``
    cell, where levels 0-1 take the BANDED tier (at the Sintel frame only
    level 0 does) and the volume it is compared with, 5.7 GB, fits for
    one pair only: the smoke fails here before the benchmark does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_ncup_tpu.ops import nconv

    tiers, corr_epe = _pallas_against_volume(
        seed, variables, hw, iters, flow_volume
    )
    hd_tiers, hd_epe = _pallas_against_volume(seed, variables, hd_hw, iters)
    check(
        hd_tiers["banded"] > 0,
        f"corr_impl=pallas at {hd_hw} dispatch {hd_tiers}: no banded level",
    )

    # The fused NConv at the widest site of the NCUP stack at the Sintel
    # crop (5x5, 2 -> 2 channels, full resolution).
    rng = np.random.default_rng(seed)
    shape = (2, nconv_hw[0], nconv_hw[1], 2)
    data = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    conf = jnp.asarray(rng.uniform(0.0, 1.0, shape), jnp.float32)
    weight = jnp.asarray(rng.uniform(0.1, 1.0, (5, 5, 2, 2)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(2), jnp.float32)
    outs = {}
    # Three twins: the kernel (float32 VPU accumulate); XLA at "highest"
    # precision, the float32 reference the kernel must match tightly; and
    # XLA as the model calls it — on the TPU its convolutions run at the
    # default (bf16-pass) precision, so that distance is reported, not
    # bounded tightly.
    for name, impl, precision in (
        ("pallas", "pallas", None),
        ("xla_highest", "xla", "highest"),
        ("xla_default", "xla", None),
    ):
        fn = jax.jit(
            lambda d, c, w, b, i=impl: nconv.nconv2d(d, c, w, b, impl=i)
        )
        nconv.reset_dispatch_counts()
        with jax.default_matmul_precision(precision):
            compiled = fn.lower(data, conf, weight, bias).compile()
        if impl == "pallas":
            sites = nconv.dispatch_counts()
            check(
                sites["fallback"] == 0 and sites["fused"] > 0,
                f"nconv impl=pallas dispatch {sites}",
            )
            check(
                "tpu_custom_call" in compiled.as_text(),
                "nconv impl=pallas: no tpu_custom_call in the compiled text",
            )
        outs[name] = [
            np.asarray(x)
            for x in jax.device_get(compiled(data, conf, weight, bias))
        ]

    def max_abs(a, b):
        return {
            k: float(np.abs(x - y).max())
            for k, x, y in zip(("out", "conf"), outs[a], outs[b])
        }

    err = max_abs("pallas", "xla_highest")
    check(
        max(err.values()) < 1e-3,
        f"nconv pallas vs float32 XLA max abs error {err}",
    )
    err_default = max_abs("pallas", "xla_default")
    check(
        max(err_default.values()) < EPE_BUDGET_PX,
        f"nconv pallas vs default-precision XLA max abs error {err_default}",
    )
    return {
        "corr_tiers": tiers,
        "epe_volume_vs_pallas_px": corr_epe,
        "hd_hw": list(hd_hw),
        "hd_corr_tiers": hd_tiers,
        "hd_epe_volume_vs_pallas_px": hd_epe,
        "nconv_shape": list(shape),
        "nconv_sites": sites,
        "nconv_max_abs_err_vs_f32_xla": err,
        "nconv_max_abs_err_vs_default_xla": err_default,
    }


def phase_mesh(devices, batch: int, hw=TRAIN_HW, iters=TRAIN_ITERS) -> dict:
    """--chips 4: the Sintel train step on make_mesh(data=2, spatial=2)
    over four chips against the same step and batch on one of them."""
    from __graft_entry__ import mesh_train_step
    from raft_ncup_tpu.parallel.mesh import collective_stats

    shape = dict(batch_size=batch, image_size=hw, iters=iters)
    _, one = mesh_train_step(devices[:1], spatial=1, **shape)
    _, four = mesh_train_step(devices[:4], spatial=2, **shape)
    rel = {
        k: abs(four[k] - one[k]) / max(abs(one[k]), 1e-12)
        for k in ("loss", "grad_norm")
    }
    check(
        all(math.isfinite(four[k]) for k in rel),
        f"mesh step non-finite: {four['loss']}, {four['grad_norm']}",
    )
    check(
        max(rel.values()) < 1e-2,
        f"mesh step differs from the one-device step: rel {rel} "
        f"(mesh {four['loss']}, {four['grad_norm']}; one device "
        f"{one['loss']}, {one['grad_norm']})",
    )
    by_op = collective_stats(four["text"])["by_op"]
    check(
        by_op["all-reduce"]["count"] > 0
        and by_op["collective-permute"]["count"] > 0,
        f"mesh step lacks all-reduce or halo collectives: {by_op}",
    )
    check(
        collective_stats(one["text"])["collectives"] == 0,
        "the one-device step holds collectives",
    )
    placed = {
        s.device.id for s in four["batch"]["image1"].addressable_shards
    }
    check(
        len(placed) == 4,
        f"batch shards sit on devices {sorted(placed)}, want 4 distinct",
    )
    return {
        "mesh": {k: int(v) for k, v in four["mesh"].shape.items()},
        "global_batch": batch,
        "loss": {"mesh": four["loss"], "one_device": one["loss"]},
        "grad_norm": {
            "mesh": four["grad_norm"], "one_device": one["grad_norm"],
        },
        "rel_diff": rel,
        "collectives": {
            k: v["count"] for k, v in by_op.items() if v["count"]
        },
        "shard_devices": sorted(placed),
    }


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--out", default=os.path.join(os.getcwd(), "chiprun_out", "smoke"),
        help="everything the smoke writes (checkpoints, flight dumps)",
    )
    args = parser.parse_args(argv)
    run_dir = os.path.join(args.out, f"run_{int(time.time())}_{os.getpid()}")
    os.makedirs(run_dir)

    import jax

    from raft_ncup_tpu.utils.profiling import compile_meter
    from raft_ncup_tpu.utils.runtime import enable_compilation_cache

    meter = compile_meter()
    devices = jax.devices()
    dev0 = devices[0]
    with phase("device", meter) as facts:
        facts.update(phase_device(devices, args.chips))
        facts["compile_cache_dir"] = enable_compilation_cache()
    hbm_gib = (dev0.memory_stats() or {}).get("bytes_limit", 0) / GIB
    batch, batch_why = pick_train_batch(hbm_gib)

    if args.chips == 4:
        # Only the multi-chip path and what it is compared with. Batch 4
        # does not leave the headroom on one chip (pick_train_batch), so
        # the global batch is 2: one sample per data shard.
        with phase("mesh", meter, dev0) as facts:
            facts.update(phase_mesh(devices, batch))
    else:
        with phase("eval", meter, dev0) as facts:
            eval_facts, variables, flow_volume = phase_eval(args.seed)
            facts.update(eval_facts)
        with phase("server", meter, dev0) as facts:
            facts.update(phase_server(args.seed, run_dir))
        with phase("warmstart", meter, dev0) as facts:
            facts.update(phase_warmstart(args.seed, variables))
        with phase("trainer", meter, dev0) as facts:
            facts.update(phase_trainer(run_dir, batch))
            facts["batch_why"] = batch_why
        with phase("kernels", meter, dev0) as facts:
            facts.update(phase_kernels(args.seed, variables, flow_volume))

    print(final_line(device_record(devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
