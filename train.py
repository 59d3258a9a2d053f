#!/usr/bin/env python
"""Training driver (reference-compatible CLI).

The TPU re-make of the reference trainer (reference: train.py:167-261):
same stages, loss, schedule, validation cadence and flag names — but the
step is one jitted SPMD program over a (data, spatial) device mesh, the
input pipeline is a host-sharded threaded loader with device-side batch
prefetch (transfer overlapped with compute; metrics accumulate on device
so the steady-state loop never syncs the host), and checkpoints carry
the full train state (params + optimizer + step) via orbax.

Fault tolerance (raft_ncup_tpu/resilience/; docs/RESILIENCE.md):

- the divergence sentinel rides inside the jitted step (non-finite or
  grad-spiking steps are skip-updates; K consecutive bad steps halt the
  run, roll back to the last good checkpoint and exit EXIT_DIVERGED);
- SIGTERM/SIGINT trigger one atomic, multihost-agreed checkpoint plus
  exact-resume metadata, then a clean exit with EXIT_PREEMPTED;
- dataset reads and checkpoint saves retry with bounded backoff, with
  per-run accounting in log.txt;
- ``--chaos`` injects deterministic faults for the resilience tests.

Example (mirrors train_raft_nc_things.sh):
    python train.py --name raft_nc_things --model raft_nc_dbl \
        --stage things --num_steps 100000 --batch_size 6 \
        --lr 0.000125 --image_size 400 720 --final_upsampling=NConvUpsampler
"""

from __future__ import annotations

import contextlib
import os
import signal
import sys

import jax
import numpy as np


def main(argv=None) -> int:
    from raft_ncup_tpu.cli import parse_train
    from raft_ncup_tpu.data import fetch_training_set
    from raft_ncup_tpu.evaluation import VALIDATORS
    from raft_ncup_tpu.parallel.mesh import make_mesh
    from raft_ncup_tpu.parallel.multihost import (
        initialize_distributed,
        is_main_process,
        is_multihost,
    )
    from raft_ncup_tpu.resilience import (
        EXIT_DIVERGED,
        EXIT_PREEMPTED,
        ChaosDataset,
        ChaosSpec,
        PreemptionHandler,
        chaos_batches,
        resume_metadata,
    )
    from raft_ncup_tpu.training.checkpoint import (
        CheckpointManager,
        load_pretrained_trunk,
    )
    from raft_ncup_tpu.training.logger import Logger
    from raft_ncup_tpu.training.loop import open_train_run, train_steps

    args, model_cfg, train_cfg, data_cfg = parse_train(argv)
    initialize_distributed()  # no-op off-pod; wires processes on a pod
    # Persistent XLA cache (utils/runtime.py has the one rule): on an
    # accelerator a kill/resume cycle hits warm executables, so resume
    # costs restore latency, not a multi-minute recompile. After the
    # distributed init: the rule asks jax for its backend.
    from raft_ncup_tpu.utils.runtime import enable_compilation_cache

    enable_compilation_cache()
    np.random.seed(train_cfg.seed)  # reference: train.py:345-346
    chaos = ChaosSpec.parse(args.chaos)

    run_dir = os.path.join(train_cfg.checkpoint_dir, train_cfg.name)
    # One writer per pod: only process 0 owns log.txt/TensorBoard (orbax
    # saves stay all-process — it coordinates its own shard writes).
    # Validation itself still runs on EVERY process: the validators
    # host-shard the frames and all-reduce the metric sums, so each
    # process computes its slice and returns identical global numbers.
    logger = Logger(
        run_dir, config=train_cfg, sum_freq=train_cfg.sum_freq,
        active=is_main_process(),
    )
    if chaos.active:
        logger.write_text(f"chaos: {chaos.render()}")

    # Device mesh: data-parallel over all chips unless told otherwise. The
    # per-step global batch must divide evenly over the data axis; when the
    # size is left implicit single-host, use the largest batch divisor that
    # fits. Multi-host, every host's chips must be in the mesh (a host with
    # no addressable mesh devices cannot feed its batch shard), so the mesh
    # always spans all devices and the batch must divide it.
    n_dev = len(jax.devices())
    multihost = is_multihost()
    if train_cfg.data_parallel:
        data_par = train_cfg.data_parallel
        if train_cfg.batch_size % data_par:
            raise SystemExit(
                f"--batch_size {train_cfg.batch_size} not divisible by "
                f"--data_parallel {data_par}"
            )
        if multihost and data_par * train_cfg.spatial_parallel != n_dev:
            raise SystemExit(
                f"multi-host mesh must span all {n_dev} devices, got "
                f"{data_par} x {train_cfg.spatial_parallel}"
            )
    else:
        data_par = max(1, n_dev // train_cfg.spatial_parallel)
        if multihost:
            if train_cfg.batch_size % data_par:
                raise SystemExit(
                    f"--batch_size {train_cfg.batch_size} must be divisible "
                    f"by the {data_par}-way data axis on a multi-host mesh"
                )
        else:
            while train_cfg.batch_size % data_par:
                data_par -= 1
    use_mesh = data_par * train_cfg.spatial_parallel > 1
    mesh = (
        make_mesh(data=data_par, spatial=train_cfg.spatial_parallel)
        if use_mesh
        else None
    )
    logger.write_text(
        f"devices={n_dev} mesh=({data_par} data x "
        f"{train_cfg.spatial_parallel} spatial)"
    )

    def restore(state):
        if train_cfg.load_pretrained:
            variables = {"params": state.params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            merged = load_pretrained_trunk(train_cfg.load_pretrained, variables)
            state = state.replace(
                params=merged["params"],
                batch_stats=merged.get("batch_stats", state.batch_stats),
            )
            logger.write_text(
                f"warm-started trunk from {train_cfg.load_pretrained}"
            )
        if train_cfg.restore_ckpt:
            same_dir = (
                os.path.abspath(train_cfg.restore_ckpt)
                == os.path.abspath(run_dir)
            )
            restore_mgr = (
                ckpt
                if same_dir
                else CheckpointManager(train_cfg.restore_ckpt, metadata=meta)
            )
            try:
                state = restore_mgr.restore(state)
            finally:
                if restore_mgr is not ckpt:
                    restore_mgr.close()
            logger.write_text(
                f"restored step {int(state.step)} from {train_cfg.restore_ckpt}"
            )
        return state

    # Exact-resume metadata rides next to every orbax payload and is
    # verified before any restore: a wrong-arch/seed resume fails with a
    # clear message, not an orbax pytree error.
    meta = resume_metadata(model_cfg, train_cfg)
    ckpt = CheckpointManager(run_dir, max_to_keep=5, metadata=meta)

    dataset = fetch_training_set(
        train_cfg.stage, train_cfg.image_size, data_cfg
    )
    if chaos.ioerror_reads:
        dataset = ChaosDataset(dataset, chaos.ioerror_reads)

    def wrap_batches(batches, start_step):
        if chaos.nan_steps:
            return chaos_batches(
                batches, chaos.nan_steps, start_step=start_step,
                log=logger.write_text,
            )
        return batches

    # State, step, loader, device prefetcher and the dispatch loop are the
    # package's (training/loop.py), shared with the benchmark's driver.
    run = open_train_run(
        model_cfg, train_cfg, data_cfg, mesh=mesh, dataset=dataset,
        restore=restore, wrap_batches=wrap_batches,
    )
    model, loader, prefetcher = run.model, run.loader, run.prefetcher
    logger.write_text(
        f"training with {len(dataset)} pairs "
        f"({len(loader)} batches/epoch/host)"
    )

    def run_validation(step: int) -> None:
        state = run.state
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        if multihost:
            # The validators host-shard the frames (mesh=None path), so
            # each host runs DIFFERENT host-local forwards. Pod-global
            # jax.Arrays must not flow in: computation-follows-data would
            # put those divergent programs on the global device
            # assignment and desynchronize the pod. Pull params to host
            # numpy so every forward is process-local.
            variables = jax.tree.map(np.asarray, variables)
        for val_set in train_cfg.validation:
            results = VALIDATORS[val_set](model, variables, data_cfg)
            logger.write_dict(step, results)

    total = train_cfg.num_steps
    start_step = run.start_step
    # --strict_guards: the invariants graftlint proves statically,
    # asserted live — implicit host pulls inside the step scope raise
    # GuardViolation immediately; steady-state recompiles fail the run at
    # the end-of-loop check. Validation/checkpointing stay outside the
    # guarded scope (they legitimately pull to host and compile new
    # shapes). See docs/ANALYSIS.md.
    step_guard = None
    guard_scope = contextlib.nullcontext
    if args.strict_guards:
        from raft_ncup_tpu.analysis.guards import StepGuard

        step_guard = StepGuard()
        guard_scope = step_guard.scope
    sentinel_on = (
        train_cfg.anomaly_sentinel and run.state.sentinel is not None
    )
    profile_scope = contextlib.ExitStack()
    loop_scope = contextlib.ExitStack()
    if step_guard is not None:
        loop_scope.enter_context(step_guard)
    # SIGTERM/SIGINT set a flag here; the loop polls it at the step
    # boundary (multihost: agreed via a fixed-cadence all-reduce so every
    # process saves the same step).
    preempt = loop_scope.enter_context(PreemptionHandler())
    # Flight recorder (observability/flight.py): every fault exit of
    # this run — sentinel halt (76), preemption drain (75) — banks one
    # bounded atomic dump under the run dir, next to the checkpoints a
    # postmortem reads anyway. Attached per-run to the process hub
    # (right before the loop, past every argument-validation exit);
    # detached in the teardown so re-entrant runs (tests) never dump
    # into a stale directory.
    from raft_ncup_tpu.observability import FlightRecorder, get_telemetry

    tel = get_telemetry()
    prev_flight = tel.flight
    if is_main_process():
        tel.flight = FlightRecorder(os.path.join(run_dir, "flight"))
    train_health = tel.health("train", fresh=True)
    status = 0
    preempted = halted = False
    train_health.ready(f"training from step {start_step}")
    def stop(step_i: int) -> bool:
        nonlocal preempted
        if step_i >= total:
            return True
        preempted = preempt.poll(step_i)
        return preempted

    def before_step(step_i: int) -> None:
        nonlocal profiling
        if args.profile_steps and step_i == start_step + 1:
            # Skip the compile step, then trace a few hot steps.
            from raft_ncup_tpu.utils.profiling import trace

            profile_scope.enter_context(
                trace(os.path.join(run_dir, "profile"))
            )
            profiling = True

    def after_step(step_i: int, metrics: dict) -> bool:
        """Chaos, profile end, sentinel, checkpoint and validation at the
        step boundary; True halts the loop (sentinel)."""
        nonlocal profiling, halted
        if step_i == start_step + 1:
            # Warm-up ends where the first step has been dispatched: the
            # one line that says where the start-up went.
            from raft_ncup_tpu.observability import startup_line

            line = startup_line()
            print(line, flush=True)
            logger.write_text(line)
        if chaos.sigterm_after == step_i:
            # Chaos harness: a REAL signal through the real handler,
            # pinned to a step boundary so tests replay exactly.
            os.kill(os.getpid(), signal.SIGTERM)
        if profiling and step_i >= start_step + 1 + args.profile_steps:
            jax.block_until_ready(metrics["loss"])
            profile_scope.close()
            profiling = False
            logger.write_text(f"profile trace written to {run_dir}/profile")
        if sentinel_on and step_i % train_cfg.sum_freq == 0:
            # The sentinel's ONLY host pull: window cadence, explicit
            # sanctioned device_get — the steady-state loop stays
            # sync-free (same contract as the Logger's boundary pull).
            sen = jax.device_get(run.state.sentinel)
            # Telemetry rides the SAME sanctioned pull: host ints
            # into gauges, never a second sync (observability/).
            tel.gauge_set("train_sentinel_skipped", int(sen["skipped"]))
            tel.gauge_set(
                "train_sentinel_consecutive", int(sen["consecutive"])
            )
            tel.gauge_set(
                "train_sentinel_ema_grad_norm", float(sen["ema_grad_norm"])
            )
            if int(sen["skipped"]):
                logger.write_text(
                    f"sentinel @ {step_i}: skipped={int(sen['skipped'])} "
                    f"consecutive={int(sen['consecutive'])} "
                    f"ema_grad_norm={float(sen['ema_grad_norm']):.4f}"
                )
            if int(sen["consecutive"]) >= train_cfg.sentinel_halt_after:
                tel.event(
                    "train_sentinel_halt", step=step_i,
                    consecutive=int(sen["consecutive"]),
                )
                train_health.halted(
                    f"sentinel: {int(sen['consecutive'])} "
                    f"consecutive bad steps @ {step_i}"
                )
                # Fault trigger: bank the timeline (sentinel gauges,
                # io-retry events, the halt event itself) before the
                # rollback + exit-76 path discards the process.
                tel.flight_dump(
                    "sentinel_halt", step=step_i,
                    consecutive=int(sen["consecutive"]),
                    skipped=int(sen["skipped"]),
                )
                halted = True
                return True
        if step_i % train_cfg.val_freq == 0 or step_i == total:
            ckpt.save(run.state)  # synchronous: committed on return
            run_validation(step_i)
        return False

    profiling = False
    try:
        train_steps(
            run, stop, logger=logger, guard_scope=guard_scope,
            before_step=before_step, after_step=after_step,
        )
        state, step_i = run.state, run.step_i
        # ---- post-loop: clean completion / preemption / sentinel halt --
        if preempted:
            # The one atomic preemption checkpoint: every process agreed
            # on this step, orbax commits the step directory atomically,
            # resume metadata rides along. Skip when the val_freq
            # boundary of this very step already saved it — orbax raises
            # StepAlreadyExists for a re-save, which would turn a clean
            # preemption into a crash exit.
            if ckpt.latest_step != step_i:
                ckpt.save(state)  # synchronous: committed on return
            train_health.draining(f"preempted @ {step_i}")
            # Fault trigger: the drain decision + the timeline that led
            # to it (preemption_signal event included), banked AFTER the
            # checkpoint commit so the dump can name a saved step.
            tel.flight_dump(
                "preemption_drain", step=step_i,
                checkpoint_step=ckpt.latest_step,
            )
            logger.write_text(
                f"preempted @ {step_i}: checkpoint saved, exiting "
                f"{EXIT_PREEMPTED}"
            )
            status = EXIT_PREEMPTED
        elif halted:
            logger.write_text(
                f"sentinel halt @ {step_i}: "
                f">={train_cfg.sentinel_halt_after} consecutive bad steps"
            )
            # Skip-updates kept the in-memory params last-good, but a
            # persistent bad streak means the run has gone wrong: roll
            # back to the last checkpoint on disk and hand the decision
            # to the operator via the distinct exit code.
            if ckpt.latest_step is not None:
                state = ckpt.restore(state)
                logger.write_text(
                    f"rolled back to last good checkpoint "
                    f"(step {int(state.step)})"
                )
            else:
                logger.write_text("no checkpoint available to roll back to")
            status = EXIT_DIVERGED
        if step_guard is not None and status == 0:
            s = step_guard.stats
            logger.write_text(
                f"strict_guards: warmup_compiles={s.warmup_compiles} "
                f"steady_recompiles={s.recompiles} "
                f"host_transfers={s.host_transfers} "
                f"sanctioned_gets={s.sanctioned_gets}"
            )
            step_guard.check()  # raises on steady-state recompilation
        # Per-run IO-fault accounting: a run that survived on retries or
        # quarantined samples says so in log.txt.
        if not loader.retry_stats.clean:
            logger.write_text("io-retry: " + loader.retry_stats.summary())
        if not ckpt.retry_stats.clean:
            logger.write_text("ckpt-retry: " + ckpt.retry_stats.summary())
    finally:
        # Teardown ONLY. The final save belongs to the clean paths above
        # (natural completion saves at the step_i == total boundary;
        # preemption saves explicitly): re-saving here after a mid-loop
        # crash would persist a possibly-inconsistent step, and a save
        # failure would shadow the loop's real exception. Closers are
        # individually shielded for the same reason — teardown noise must
        # never outrank the error that got us here.
        for closer in (
            loop_scope.close,
            profile_scope.close,
            prefetcher.close,
            ckpt.close,
            logger.close,
        ):
            try:
                closer()
            except Exception as e:
                print(f"teardown ({closer.__qualname__}): {e}",
                      file=sys.stderr)
        # Detach this run's flight recorder (re-entrant runs must not
        # dump into a finished run's directory).
        get_telemetry().flight = prev_flight
    if status == 0:
        print(f"done: {int(state.step)} steps, checkpoints in {run_dir}")
    else:
        kind = "preempted" if preempted else "diverged"
        print(
            f"{kind}: exiting {status} at step {step_i}, "
            f"checkpoints in {run_dir}"
        )
    return status


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
