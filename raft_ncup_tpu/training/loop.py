"""The training loop, shared by ``train.py`` and the benchmark's training
driver (``benchmark/drivers/train_steps.py``): :func:`open_train_run`
builds state, step, loader and ``DevicePrefetcher``; :func:`train_steps`
dispatches steps until told to stop.

The loop is closed on the device through a ``DispatchThrottle``: after a
step is enqueued the host waits until at most ``inflight - 1`` earlier
steps are unfinished, so it stays a bounded distance ahead (one queued
step on an accelerator, none on the CPU backend) instead of as far as
the runtime's own queue lets it run. The wait is on a step's scalar loss
(a sync, not a transfer; the state itself is donated to the next step).

Spans and counters (docs/OBSERVABILITY.md), on the process hub and, in a
capture, on the profiler's host plane: ``train_dispatch`` (the jitted
step's enqueue alone), ``train_throttle_wait`` (where the loop waits for
the device), the prefetcher's ``input_wait`` / ``input_stage`` /
``input_h2d``, the logger's ``train_metrics_pull``; ``train_steps_total``
and ``train_pairs_total`` grow where ``train_dispatch`` closes, and with
them ``train_bn_stat_updates_total`` (BatchNorm layers whose running
statistics the dispatched step replaced: what the traced step says of
itself, ``parallel/step.py``) beside the gauge ``train_bn_layers_training``
(0 on every step that freezes BatchNorm). Start-up
phases ("Start-up timeline" there), each once per run: ``startup_weights``
(:func:`open_train_run`), the step's ``startup_trace_lower`` and
``startup_compile`` (:func:`_compile_step`), its ``startup_first_run``
(:meth:`TrainRun.step`) and the prefetcher's ``input_start``.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax

from raft_ncup_tpu.config import DataConfig, ModelConfig, TrainConfig


@dataclass
class TrainRun:
    """What a training loop holds between steps. ``state`` is the newest
    train state (the previous one is donated to each step)."""

    model: Any
    state: Any
    step_fn: Callable
    schedule: Callable
    loader: Any
    prefetcher: Any
    throttle: Any
    train_cfg: TrainConfig
    step_i: int  # host-side counter; int(state.step) would sync
    start_step: int
    _compiled: Any = None  # the step's executable, from its first call on

    def step(self, state, batch, rng):
        """One call of the compiled step: ``(state, metrics)``. ``state``
        is donated."""
        if self._compiled is None:
            self._compiled, fresh_key = _compile_step(
                self.step_fn, self.train_cfg, state, batch, rng
            )
            if fresh_key is not None:
                # The first dispatch of a step this run compiled, as the
                # start-up phase ``startup_first_run`` (the loop's
                # ``train_throttle_wait`` is where the device is waited for).
                from raft_ncup_tpu.inference.costs import first_run, get_cost_ledger
                from raft_ncup_tpu.observability import get_telemetry

                return first_run(
                    get_cost_ledger(), get_telemetry(), self._compiled,
                    (state, batch, rng), fresh_key, "train_step",
                )
        return self._compiled(state, batch, rng)

    def close(self) -> None:
        self.prefetcher.close()


# Executables by (jitted step, argument shapes): an in-process resume
# (resilience tests, notebook restarts) finds the one its first run compiled,
# as it finds the jitted step itself (parallel/step.py). Bounded FIFO.
_COMPILED: dict = {}
_COMPILED_MAX = 8


def _compile_step(step_fn, cfg: TrainConfig, *args) -> tuple:
    """The first step AOT-compiles the jitted step (``lower().compile()``:
    still exactly one XLA compile) and banks the executable in the
    process's cost ledger, which gives a capture the step's operations by
    scope (``utils/profiling.trace`` writes the ledger's ``op_scopes``
    beside it), ``memory_analysis()`` to whoever asks, and the start-up
    timeline the build's two phases (``costs.build_and_record``). Where
    the probe fails, or on a pod, the run goes through the jitted function
    itself. Returns ``(callable, ledger key)``; the key is ``None`` unless
    the executable was built by this call."""
    from raft_ncup_tpu.inference.costs import build_and_record, get_cost_ledger
    from raft_ncup_tpu.observability import get_telemetry

    ledger = get_cost_ledger()
    if not ledger.enabled or jax.process_count() > 1:
        return step_fn, None
    key = (step_fn, jax.tree.structure(args), tuple(
        (x.shape, str(x.dtype)) for x in jax.tree.leaves(args)
    ))
    if key in _COMPILED:
        return _COMPILED[key], None
    ledger_key = (
        f"{jax.default_backend()}|train_step|{cfg.stage}|{cfg.batch_size}"
        f"x{cfg.image_size[0]}x{cfg.image_size[1]}|{cfg.iters}|{cfg.precision}"
    )
    try:
        compiled = build_and_record(
            ledger, get_telemetry(), step_fn, args, ledger_key,
            backend=jax.default_backend(), kind="train_step",
            shape=(cfg.batch_size, *cfg.image_size, 3), iters=cfg.iters,
            policy=cfg.precision,
        )
    except Exception as e:  # the probe must not be able to stop a run
        print(f"train step: cost probe unavailable ({e}); plain jit", flush=True)
        return step_fn, None
    while len(_COMPILED) >= _COMPILED_MAX:
        _COMPILED.pop(next(iter(_COMPILED)))
    _COMPILED[key] = compiled
    return compiled, ledger_key


def open_train_run(
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    data_cfg: DataConfig,
    *,
    mesh=None,
    dataset=None,
    variables: Optional[dict] = None,
    restore: Optional[Callable] = None,
    wrap_batches: Optional[Callable] = None,
) -> TrainRun:
    """Build everything a run of :func:`train_steps` needs.

    ``dataset``: the training set (None: the stage's mixture,
    ``fetch_training_set``). ``variables``: initial weights in the
    checkpoint layout (``params`` / ``batch_stats``), in place of a fresh
    init. ``restore(state) -> state``: warm start or checkpoint restore,
    applied before the state is committed to the device.
    ``wrap_batches(batches, start_step) -> batches``: a wrapper of the
    host-batch stream (the chaos harness).
    """
    from raft_ncup_tpu.data import DevicePrefetcher, FlowLoader, fetch_training_set
    from raft_ncup_tpu.inference.pipeline import DispatchThrottle
    from raft_ncup_tpu.observability import (
        StartupPhase,
        get_startup_record,
        get_telemetry,
    )
    from raft_ncup_tpu.parallel.mesh import batch_sharding, replicated
    from raft_ncup_tpu.parallel.multihost import is_multihost
    from raft_ncup_tpu.parallel.step import make_train_step
    from raft_ncup_tpu.training.optim import build_schedule
    from raft_ncup_tpu.training.state import create_train_state
    from raft_ncup_tpu.utils.profiling import annotate_spans, compile_meter

    tel = get_telemetry()
    annotate_spans(tel)
    compile_meter()  # the process's compile listener counts from here on
    # The train state from the caller's tree to the device, as one
    # start-up phase: state build, optimizer init, restore, commit. It
    # ends where the commit is enqueued and the restored step is read
    # (the one leaf this function already waits for).
    with StartupPhase(tel, "startup_weights") as weights:
        model, state = create_train_state(
            jax.random.PRNGKey(train_cfg.seed), model_cfg, train_cfg,
            variables=variables,
        )
        if restore is not None:
            state = restore(state)
        if not is_multihost():
            # Commit the state to where the step leaves its output. A fresh
            # (uncommitted) state and the step's own committed output are two
            # jit signatures, and the train program was compiled once for
            # each: ~4 extra minutes at every start on the chip (first chip
            # run, PR 21: 172 compiles, 2 x ~245 s in one trainer).
            state = jax.device_put(
                state,
                replicated(mesh) if mesh is not None else jax.devices()[0],
            )
        step_i = int(state.step)
        weights.set(bytes=sum(
            getattr(x, "nbytes", 0) for x in jax.tree.leaves(state)
        ))
    get_startup_record().phase("weights_s", weights.seconds)
    step_fn = make_train_step(model, train_cfg, mesh=mesh)

    if dataset is None:
        dataset = fetch_training_set(
            train_cfg.stage, train_cfg.image_size, data_cfg
        )
    # --batch_size is the GLOBAL batch (reference semantics); each host
    # loads its slice.
    n_proc = jax.process_count()
    if train_cfg.batch_size % n_proc:
        raise SystemExit(
            f"--batch_size {train_cfg.batch_size} not divisible by "
            f"{n_proc} hosts"
        )
    loader = FlowLoader(
        dataset,
        batch_size=train_cfg.batch_size // n_proc,
        seed=train_cfg.seed,
        num_workers=data_cfg.num_workers,
        prefetch=data_cfg.prefetch,
        io_retries=data_cfg.io_retries,
        io_retry_backoff_s=data_cfg.io_retry_backoff_s,
    )
    # Batch shardings feed the device prefetcher on every mesh run (not
    # just multihost): single-process device_put straight into the step's
    # input layout means jit dispatch never re-lays-out the batch.
    shardings = batch_sharding(mesh) if mesh is not None else None

    # Resume the data stream where the restored run left off: the loader
    # is deterministic per (seed, epoch, index), so the (epoch, batch)
    # position is derived from the restored step and the intra-epoch
    # batches already consumed are skipped without loading.
    per_epoch = max(len(loader), 1)
    batches = loader.batches(
        start_epoch=step_i // per_epoch, start_batch=step_i % per_epoch
    )
    if wrap_batches is not None:
        batches = wrap_batches(batches, step_i)
    # Async input pipeline: a worker thread moves host batches onto device
    # (into the step's batch sharding) depth>=2 steps ahead, so in steady
    # state next() hands back an already-device-resident batch and the
    # loop's only work between dispatches is the rng fold-in.
    prefetcher = DevicePrefetcher(
        batches,
        depth=data_cfg.device_prefetch,  # <2 trades overlap for HBM headroom
        mesh=mesh,
        shardings=shardings,
    )
    return TrainRun(
        model=model, state=state, step_fn=step_fn,
        schedule=build_schedule(train_cfg), loader=loader,
        prefetcher=prefetcher, throttle=DispatchThrottle(),
        train_cfg=train_cfg, step_i=step_i, start_step=step_i,
    )


def train_steps(
    run: TrainRun,
    stop: Callable[[int], bool],
    *,
    logger=None,
    guard_scope: Callable = contextlib.nullcontext,
    before_step: Optional[Callable[[int], None]] = None,
    after_step: Optional[Callable[[int, dict], bool]] = None,
) -> None:
    """Dispatch training steps until ``stop(step_i)`` says so (asked before
    each step) or ``after_step(step_i, metrics)`` returns True (called after
    each, with the count of steps dispatched and that step's device
    scalars). On return every dispatched step has finished on the device.

    ``guard_scope``: a context manager factory entered around each step's
    input, dispatch and logging (``--strict_guards``).
    """
    from raft_ncup_tpu.observability import get_telemetry
    from raft_ncup_tpu.utils.profiling import annotate_spans

    tel = get_telemetry()
    annotate_spans(tel)
    cfg = run.train_cfg
    while not stop(run.step_i):
        if before_step is not None:
            before_step(run.step_i)
        with guard_scope():
            device_batch = next(run.prefetcher)
            rng = jax.random.fold_in(
                jax.random.PRNGKey(cfg.seed), run.step_i
            )
            with tel.span("train_dispatch", step=run.step_i, precision=cfg.precision):
                run.state, metrics = run.step(run.state, device_batch, rng)
            tel.inc("train_steps_total")
            tel.inc("train_pairs_total", cfg.batch_size)
            bn_layers = run.step_fn.report["bn_layers_training"]  # traced by now
            tel.inc("train_bn_stat_updates_total", bn_layers)
            tel.gauge_set("train_bn_layers_training", bn_layers)
            run.step_i += 1
            with tel.span("train_throttle_wait", step=run.step_i - 1):
                run.throttle.push(metrics["loss"])
            if logger is not None:
                logger.push(
                    run.step_i - 1, metrics,
                    lr=run.schedule(run.step_i - 1),
                )
        if after_step is not None and after_step(run.step_i, metrics):
            break
    run.throttle.drain()
