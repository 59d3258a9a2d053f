"""The flow-serving front-end: dynamic micro-batching over a bounded
executable set, with admission control, deadlines, anytime iteration
budgets, poison quarantine, and graceful drain.

Data path (one dispatcher thread, clients on their own threads):

1. **submit** (client thread): cheap metadata validation (ndim / dtype /
   size caps — malformed requests are ``rejected`` before they occupy
   queue capacity; the default size ceiling is UHD 2176x3840, servable
   since the banded corr tier broke the 4K memory wall — docs/PERF.md
   "Banded dispatch"), pad-spec computation (``InputPadder`` with the
   configured bucket, so the request's batching key is its PADDED
   shape), then a non-blocking ``AdmissionQueue.offer`` — a full queue
   sheds with a ``retry_after_s`` hint derived from the live service-
   time EMA.
2. **assemble** (dispatcher): pop a FIFO run of same-padded-shape
   requests, expire the ones past their deadline (``timeout``, zero
   compute), scan the survivors' pixels for non-finite values — a NaN
   input is *quarantined alone* (``rejected`` + ``ServeStats``
   accounting, the ``resilience/retry.py`` discipline) while its
   batch-mates proceed untouched.
3. **budget**: one ``IterationBudgetController.decide`` per batch with
   the queue depth just observed — under burst the GRU iteration count
   steps down a fixed level set (coarser but valid flow; RAFT's anytime
   property), with hysteresis on the way back up.
4. **stage + dispatch**: host-side ``np.pad`` to the padded shape (host
   pad, not ``jnp.pad`` — the staging path must not compile tiny device
   programs), zero-row batch padding up to the nearest allowed batch
   size, then ``ShapeCachedForward.forward_device`` — one compiled
   program per (padded shape, batch size, iters), LRU-bounded, with
   ``DispatchThrottle`` capping in-flight programs per backend.
5. **complete** (drain worker): ``AsyncDrain`` waits for the batch's
   program, performs the sanctioned ``jax.device_get`` off the dispatch
   thread, and the callback unpads each row back to its native shape
   (host slicing) and completes the request's handle with latency
   accounting — three spans per batch (``serve_device_wait``,
   ``serve_pull``, ``serve_deliver``; docs/OBSERVABILITY.md).

**Drain contract** (``drain()``, reused by serve.py's SIGTERM path via
``resilience/preemption.PreemptionHandler``): stop admitting (new
submits shed with ``detail="draining"``), flush every request already
admitted — through compute, not dropped — then tear down the dispatcher
and drain worker and return the final ``ServeStats``. Nothing admitted
is ever silently lost; everything refused is told so explicitly.

Invariants inherited from the rest of the stack: the steady-state
serving loop performs zero implicit host transfers and zero recompiles
(tests/test_serving.py pins both under ``analysis/guards.py``). The
per-batch result pull is the *product* here, not a leak — it flows
through the one sanctioned explicit ``jax.device_get`` in the
``AsyncDrain`` worker.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

import numpy as np

from raft_ncup_tpu.config import ServeConfig
from raft_ncup_tpu.inference.pipeline import (
    AsyncDrain,
    DispatchThrottle,
    ShapeCachedForward,
    env_earlyexit_tol,
)
from raft_ncup_tpu.observability import (
    StartupPhase,
    get_startup_record,
    get_telemetry,
    startup_report,
)
from raft_ncup_tpu.ops.padding import InputPadder
from raft_ncup_tpu.serving.admission import AdmissionQueue
from raft_ncup_tpu.serving.budget import IterationBudgetController
from raft_ncup_tpu.serving.request import (
    STATUS_ERROR,
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHED,
    STATUS_TIMEOUT,
    FlowRequest,
    FlowResponse,
    ServeHandle,
    ServeStats,
)

_POLL_S = 0.05  # dispatcher wake cadence while the queue is idle


class FlowServer:
    """Serve flow requests against one model + variables set.

    ``clock`` is injectable (tests drive deadlines deterministically);
    it must be monotonic. The server owns one dispatcher thread from
    construction until :meth:`drain`.
    """

    def __init__(
        self,
        model,
        variables: dict,
        cfg: Optional[ServeConfig] = None,
        *,
        mesh=None,
        clock: Callable[[], float] = time.monotonic,
        telemetry=None,
    ):
        self.cfg = cfg or ServeConfig()
        self._clock = clock
        # The telemetry hub (observability/; docs/OBSERVABILITY.md):
        # stats mirror into its registry under the canonical counter
        # names, spans trace each batch's queue-wait / assembly /
        # pad+stage / dispatch / drain stages with request/batch
        # correlation ids, and report() reads the per-stage p50/p99
        # back out. None binds the process-wide default hub.
        self._tel = telemetry if telemetry is not None else get_telemetry()
        self.stats = ServeStats(telemetry=self._tel)
        # The machine-readable health answer (observability/health.py;
        # docs/OBSERVABILITY.md): STARTING here, WARMING/READY through
        # warmup (or READY at the first completed batch), READY ⇄
        # DEGRADED driven by the hub's SLO verdicts, DRAINING in
        # drain() — the exact scrape surface serve.py --healthz_file
        # exposes to a fleet router.
        self.health = self._tel.health("serve", fresh=True)
        # Mesh-first serving (docs/SHARDING.md): an explicit `mesh=`
        # wins; otherwise ServeConfig.mesh = (data, spatial) builds one.
        # Every compiled serving program is then a single SPMD program —
        # batches sharded over `data`, image height over `spatial` — and
        # request pads round up to the mesh divisor.
        from raft_ncup_tpu.parallel.mesh import resolve_config_mesh

        mesh, self._pad_divisor = resolve_config_mesh(mesh, self.cfg.mesh)
        self.mesh = mesh
        # The per-ServeConfig precision policy (docs/PRECISION.md): every
        # compiled serving program — warmup set included — runs under it,
        # and its fingerprint rides every executable key. None inherits
        # the model's own policy (ShapeCachedForward's default).
        self._fwd = ShapeCachedForward(
            model, variables, mesh=mesh, cache_size=self.cfg.cache_size,
            policy=self.cfg.precision, telemetry=self._tel,
        )
        self._queue = AdmissionQueue(
            self.cfg.queue_capacity, telemetry=self._tel, name="serve"
        )
        self.budget = IterationBudgetController(
            self.cfg.iter_levels,
            capacity=self.cfg.queue_capacity,
            high_water=self.cfg.high_water,
            low_water=self.cfg.low_water,
            recover_patience=self.cfg.recover_patience,
        )
        # Early exit (docs/PERF.md "Early exit"): resolved from the env
        # knobs ONCE at construction — executable identity must not flip
        # mid-run with the environment. None = detection off, the exact
        # pre-early-exit serving path and executables.
        self._earlyexit_tol = env_earlyexit_tol()
        self._throttle = DispatchThrottle(self.cfg.inflight)
        self._drainer = AsyncDrain(depth=self.cfg.drain_depth)
        self._handles: dict[int, ServeHandle] = {}
        # Batches handed to the AsyncDrain worker and not yet delivered:
        # the safety net that keeps a drain-worker failure (device_get
        # error, callback bug) from leaving handles uncompleted forever
        # — AsyncDrain surfaces worker errors from a LATER submit/close,
        # so without this registry the error would be attributed to the
        # wrong batch and the failed batch's clients would hang.
        self._inflight: dict[int, list] = {}
        self._inflight_seq = 0
        self._inflight_lock = threading.Lock()
        self._service_ema: Optional[float] = None  # seconds per pair
        self._ema_lock = threading.Lock()
        self._next_id = 0
        self._id_lock = threading.Lock()
        # The warmed (padded_h, padded_w, batch, iters) executable set,
        # recorded by warmup(): the replica identity a fleet router
        # routes shape-aware against (serve.py threads it into the
        # healthz file via Telemetry.identity; docs/FLEET.md).
        self.warmed: list = []
        self._draining = threading.Event()
        self._drained = False
        self._thread = threading.Thread(
            target=self._dispatch_loop, name="flow-serve-dispatch",
            daemon=True,
        )
        self._thread.start()

    # ------------------------------------------------------------ admission

    def submit(
        self,
        image1,
        image2,
        *,
        deadline_s: Optional[float] = None,
        request_id: Optional[int] = None,
        trace_id: Optional[str] = None,
    ) -> ServeHandle:
        """Submit one frame pair; returns immediately with a handle.

        The handle completes with exactly one terminal status (see
        ``serving/request.py``). ``deadline_s`` is seconds from now
        (default ``cfg.default_deadline_s``; ``None`` = no deadline).
        ``request_id`` lets a fleet router supply ITS correlation id as
        the request's identity — the replica-side spans then carry the
        router-side id verbatim, so one ``request_id`` reassembles the
        journey across the process boundary (docs/FLEET.md;
        scripts/postmortem.py). Caller owns uniqueness. ``trace_id``
        adopts an inbound cross-process trace context: every span this
        request touches carries it, so the fleet's one-trace-per-request
        contract holds on the replica side too.
        """
        self.stats.note_submitted()
        handle = ServeHandle()
        if request_id is not None:
            rid = int(request_id)
        else:
            with self._id_lock:
                rid = self._next_id
                self._next_id += 1
        if self._draining.is_set():
            self.stats.note_shed()
            handle.complete(FlowResponse(
                rid, STATUS_SHED, retry_after_s=self._retry_after(),
                detail="draining",
            ))
            return handle
        err = self._admission_error(image1) or self._admission_error(image2)
        if err is None and image1.shape != image2.shape:
            err = f"frame shapes differ: {image1.shape} vs {image2.shape}"
        if err is not None:
            self.stats.note_rejected(rid)
            handle.complete(FlowResponse(rid, STATUS_REJECTED, detail=err))
            return handle
        h, w = int(image1.shape[0]), int(image1.shape[1])
        padder = InputPadder((h, w, 3), mode="sintel",
                             divisor=self._pad_divisor,
                             bucket=self.cfg.pad_bucket)
        (t, b), (le, r) = padder.pad_spec
        deadline_s = (
            deadline_s if deadline_s is not None
            else self.cfg.default_deadline_s
        )
        now = self._clock()
        req = FlowRequest(
            request_id=rid,
            image1=image1,
            image2=image2,
            deadline=None if deadline_s is None else now + deadline_s,
            submit_time=now,
            shape_key=(h + t + b, w + le + r),
            pad_spec=padder.pad_spec,
            native_hw=(h, w),
            trace_id=None if trace_id is None else str(trace_id),
        )
        self._handles[rid] = handle
        if not self._queue.offer(req):
            self._handles.pop(rid, None)
            self.stats.note_shed()
            handle.complete(FlowResponse(
                rid, STATUS_SHED, retry_after_s=self._retry_after(),
                detail="admission queue full",
            ))
            return handle
        self.stats.note_accepted()
        return handle

    def _admission_error(self, image) -> Optional[str]:
        shape = getattr(image, "shape", None)
        dtype = getattr(image, "dtype", None)
        if shape is None or dtype is None:
            return f"not an array: {type(image).__name__}"
        if len(shape) != 3 or shape[-1] != 3:
            return f"want (H, W, 3), got shape {tuple(shape)}"
        if np.dtype(dtype).kind not in "uif":
            return f"non-numeric dtype {dtype}"
        h, w = int(shape[0]), int(shape[1])
        mh, mw = self.cfg.max_image_hw
        if h < self.cfg.min_image_hw or w < self.cfg.min_image_hw:
            return f"image {h}x{w} below minimum {self.cfg.min_image_hw}"
        if h > mh or w > mw:
            return f"image {h}x{w} exceeds maximum {mh}x{mw}"
        return None

    def _retry_after(self) -> float:
        with self._ema_lock:
            per_pair = self._service_ema
        if per_pair is None:
            return self.cfg.default_retry_after_s
        # Time for the current backlog to clear is the honest hint.
        return round((len(self._queue) + 1) * per_pair, 4)

    # ------------------------------------------------------------- dispatch

    def _dispatch_loop(self) -> None:
        while True:
            batch = self._queue.pop_batch(self.cfg.max_batch,
                                          timeout=_POLL_S)
            if not batch:
                if self._queue.closed and not len(self._queue):
                    return
                continue
            depth = len(self._queue) + len(batch)
            try:
                self._process(batch, depth)
            except BaseException as e:  # noqa: BLE001 — per-request status
                # The fault is the server's (XLA error, drain-worker
                # failure...): every still-pending request in the batch
                # gets an explicit `error` terminal status (requests the
                # batch already resolved — timeouts, rejects — keep
                # theirs); the server keeps serving later batches. A
                # drain-WORKER error re-raises from a later submit, so
                # the batches it actually stranded are flushed from the
                # in-flight registry, not blamed on this batch alone.
                self._fail_inflight(e)
                for req in batch:
                    if self._complete(req.request_id, FlowResponse(
                        req.request_id, STATUS_ERROR, detail=repr(e),
                    )):
                        self.stats.note_error()

    def _process(self, batch: list, depth: int) -> None:
        # Batch correlation id, minted up front so every span and event
        # of this batch's journey carries it (the drain worker reuses it
        # as the in-flight registry token).
        with self._inflight_lock:
            token = self._inflight_seq
            self._inflight_seq += 1
        now = self._clock()
        live = []
        with self._tel.span(
            "serve_batch_assembly", batch_id=token, batch_size=len(batch)
        ):
            for req in batch:
                if req.deadline is not None and now > req.deadline:
                    self.stats.note_timeout()
                    self._complete(req.request_id, FlowResponse(
                        req.request_id, STATUS_TIMEOUT,
                        latency_s=now - req.submit_time,
                        detail="deadline expired in queue",
                    ))
                    continue
                # Per-request queue wait (submit -> batch assembly),
                # correlated to both the request and the batch. Recorded
                # for every request that reached assembly alive —
                # including one about to be quarantined, whose journey
                # the flight recorder must still reassemble.
                self._tel.observe_ms(
                    "serve_queue_wait", (now - req.submit_time) * 1e3,
                    request_id=req.request_id, batch_id=token,
                    **({"trace_id": req.trace_id}
                       if req.trace_id is not None else {}),
                )
                poison = self._poison_error(req)
                if poison is not None:
                    self.stats.note_rejected(
                        req.request_id, quarantine=True
                    )
                    # Fault trigger: the quarantine decision plus the
                    # recent timeline, banked before the batch-mates'
                    # dispatch overwrites the ring's oldest entries.
                    self._tel.flight_dump(
                        "poison_quarantine",
                        request_id=req.request_id, batch_id=token,
                        detail=poison,
                    )
                    self._complete(req.request_id, FlowResponse(
                        req.request_id, STATUS_REJECTED, detail=poison,
                    ))
                    continue
                live.append(req)
        if not live:
            return
        # First assembly of a server that never warmed up: it is
        # serving, so it is READY. Guarded on the pre-ready states only
        # — an unconditional ready() here would undo an SLO-driven
        # DEGRADED on the very next batch.
        if self.health.state in ("starting", "warming"):
            self.health.ready("serving")
        # The budget decision reads BOTH degrade inputs: the queue depth
        # the dispatcher just observed, and the hub's SLO verdict — the
        # telemetry loop driving the anytime knob (docs/OBSERVABILITY.md).
        iters = self.budget.decide(
            depth, slo_degraded=self._tel.slo_paging("serve")
        )
        self._tel.gauge_set("serve_iter_budget", iters)
        ph, pw = live[0].shape_key
        with self._tel.span(
            "serve_pad_stage", batch_id=token, rows=len(live),
        ) as stage_span:
            rows1 = [self._stage(r.image1, r.pad_spec) for r in live]
            rows2 = [self._stage(r.image2, r.pad_spec) for r in live]
            n_rows = next(
                b for b in self.cfg.batch_sizes if b >= len(live)
            )
            pad_rows = n_rows - len(live)
            for _ in range(pad_rows):
                rows1.append(np.zeros((ph, pw, 3), np.float32))
                rows2.append(np.zeros((ph, pw, 3), np.float32))
            stage_span.set(pad_rows=pad_rows)
            img1 = np.stack(rows1)
            img2 = np.stack(rows2)
        self.stats.note_batch(pad_rows)
        t_dispatch = self._clock()
        # The dispatch span times the host-to-device copy and the jit
        # dispatch alone; the throttle's bounded wait (one batch time on
        # a saturated accelerator: inflight 2) has its own span, and the
        # drain worker's three (device wait, pull, deliver) follow under
        # the same batch id. With the wait in the drainer's queue they
        # tile the externally timed ``serve_drain`` interval (dispatch ->
        # the top of deliver). ``serve_dispatch`` carries the full
        # correlation set — request ids, batch id, mesh + policy
        # fingerprints.
        trace_ids = [r.trace_id for r in live if r.trace_id is not None]
        ee_tol = self._earlyexit_tol
        with self._tel.span(
            "serve_dispatch",
            batch_id=token,
            request_ids=[r.request_id for r in live],
            iters=iters,
            mesh=self._fwd.mesh_fp,
            policy=self._fwd.policy.name,
            **({"trace_ids": trace_ids} if trace_ids else {}),
            **({"earlyexit_tol": ee_tol} if ee_tol is not None else {}),
        ):
            if ee_tol is not None:
                # Detection on: the executed-iters counter rides the
                # SAME drain tree as the flow — the per-batch summary
                # reaches the host through the one sanctioned pull, no
                # second sync, no extra executable output path.
                _, flow_up, exec_iters = self._fwd.forward_device(
                    img1, img2, iters, early_exit_tol=ee_tol
                )
                drain_tree = (flow_up, exec_iters)
            else:
                _, flow_up = self._fwd.forward_device(img1, img2, iters)
                drain_tree = flow_up
        with self._tel.span("serve_throttle_wait", batch_id=token):
            self._throttle.push(flow_up)
        with self._inflight_lock:
            self._inflight[token] = live

        def deliver(host_out, live=live, iters=iters, token=token):
            with self._inflight_lock:
                self._inflight.pop(token, None)
            done = self._clock()
            if ee_tol is not None:
                host_flow, host_exec = host_out
            else:
                host_flow, host_exec = host_out, None
            # Dispatch -> delivered: device compute + the sanctioned
            # drain-worker pull, one per batch. The pull counter is the
            # independent measurement flip_recommendations checks
            # against stats.batches for snapshot consistency.
            self._tel.inc("serve_drain_pulls_total")
            tids = [r.trace_id for r in live if r.trace_id is not None]
            exec_attrs = {}
            if host_exec is not None:
                # Executed-iters summary over the LIVE rows only — the
                # zero batch-pad rows converge instantly and would bias
                # the mean the controller budgets from.
                live_exec = np.asarray(host_exec)[: len(live)]
                exec_attrs = {
                    "iters_budgeted": iters,
                    "iters_executed_mean": round(
                        float(live_exec.mean()), 3
                    ),
                }
            self._tel.observe_ms(
                "serve_drain", (done - t_dispatch) * 1e3,
                batch_id=token,
                request_ids=[r.request_id for r in live],
                **({"trace_ids": tids} if tids else {}),
                **exec_attrs,
            )
            if host_exec is not None:
                for k in range(len(live)):
                    self._tel.hist_observe(
                        "serve_exec_iters", float(live_exec[k])
                    )
                self.budget.note_executed(float(live_exec.mean()))
            for k, req in enumerate(live):
                (t, b), (le, r) = req.pad_spec
                hh, ww = host_flow.shape[1], host_flow.shape[2]
                flow = host_flow[k, t: hh - b, le: ww - r, :]
                self.stats.note_completed()
                # Per-request end-to-end latency (submit → delivered):
                # the SLI behind the serve_p99_latency SLO — histogram
                # only, no ring record (observability/slo.py).
                self._tel.hist_observe(
                    "serve_e2e_ms", (done - req.submit_time) * 1e3
                )
                self._complete(req.request_id, FlowResponse(
                    req.request_id, STATUS_OK, flow=flow, iters=iters,
                    latency_s=done - req.submit_time,
                ))
            # Dispatch->delivery over the batch rows: the per-pair
            # SERVICE time. Measuring from submit_time would fold queue
            # wait into the EMA and make the shed hint double-count the
            # backlog exactly when sheds happen.
            self._note_service((done - t_dispatch) / len(live))

        self._drainer.submit(
            drain_tree, deliver,
            span=lambda stage: self._tel.span(
                "serve_" + stage, batch_id=token
            ),
        )

    def _fail_inflight(self, exc: BaseException) -> None:
        """Complete every batch stranded by a drain-worker failure with
        an explicit `error` — the no-silent-loss half of the drain
        contract when the sanctioned pull itself is what broke."""
        with self._inflight_lock:
            stranded = list(self._inflight.values())
            self._inflight.clear()
        for live in stranded:
            for req in live:
                if self._complete(req.request_id, FlowResponse(
                    req.request_id, STATUS_ERROR,
                    detail=f"result drain failed: {exc!r}",
                )):
                    self.stats.note_error()

    def _poison_error(self, req: FlowRequest) -> Optional[str]:
        for name, img in (("image1", req.image1), ("image2", req.image2)):
            arr = np.asarray(img)
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                return f"non-finite pixels in {name}"
        return None

    def _stage(self, image, pad_spec) -> np.ndarray:
        (t, b), (le, r) = pad_spec
        arr = np.asarray(image, np.float32)
        if t or b or le or r:
            arr = np.pad(arr, ((t, b), (le, r), (0, 0)), mode="edge")
        return arr

    def _complete(self, rid: int, response: FlowResponse) -> bool:
        """Deliver ``response`` if ``rid`` is still pending; True when a
        handle was actually completed (each request resolves once)."""
        handle = self._handles.pop(rid, None)
        if handle is None:
            return False
        handle.complete(response)
        return True

    def _note_service(self, per_pair_s: float) -> None:
        with self._ema_lock:
            prev = self._service_ema
            self._service_ema = (
                per_pair_s if prev is None
                else 0.8 * prev + 0.2 * per_pair_s
            )
            ema = self._service_ema
        # The live EMA behind retry_after_s, as a gauge: the backpressure
        # hint's basis is observable instead of inferable from hints.
        self._tel.gauge_set("serve_service_time_ema_ms", ema * 1e3)

    # ------------------------------------------------------------- lifecycle

    def warmup(self, size_hw: tuple) -> int:
        """Compile the full executable set for one native shape: every
        (batch size, iteration level) program at its padded/bucketed
        shape. Returns the number of programs compiled. Call before a
        latency-sensitive window so no request pays a compile — with pad
        bucketing, one warmup covers every native shape in the bucket.
        """
        import jax

        self.health.warming()
        h, w = size_hw
        padder = InputPadder((int(h), int(w), 3), mode="sintel",
                             divisor=self._pad_divisor,
                             bucket=self.cfg.pad_bucket)
        (t, b), (le, r) = padder.pad_spec
        ph, pw = int(h) + t + b, int(w) + le + r
        before = self._fwd.stats["compiles"]
        warmed = []
        # The whole warm-up as one start-up phase, parent of the
        # per-executable phases it causes (docs/OBSERVABILITY.md
        # "Start-up timeline").
        with StartupPhase(self._tel, "startup_warmup") as phase:
            for n in self.cfg.batch_sizes:
                zeros = np.zeros((n, ph, pw, 3), np.float32)
                for iters in self.cfg.iter_levels:
                    # Warm the exact program the dispatch path will run —
                    # with detection on, that is the early-exit executable
                    # (no request must ever pay its compile).
                    out = self._fwd.forward_device(
                        zeros, zeros, iters,
                        early_exit_tol=self._earlyexit_tol,
                    )
                    jax.block_until_ready(out)
                    warmed.append((ph, pw, n, iters))
            compiled = self._fwd.stats["compiles"] - before
            phase.set(programs=compiled)
        get_startup_record().phase("warmup_s", phase.seconds)
        self.warmed = warmed
        self.health.ready(f"warmup compiled {compiled} programs")
        return compiled

    def pause(self) -> None:
        """Test/ops hook: stop assembling new batches (in-flight ones
        finish). Queued and newly admitted requests wait. Deterministic:
        a pause that happens-before a submit is guaranteed to beat the
        dispatcher to it (the flag lives inside the queue's condition
        predicate — see AdmissionQueue.set_paused)."""
        self._queue.set_paused(True)

    def resume(self) -> None:
        self._queue.set_paused(False)

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def drain(self, timeout: Optional[float] = None) -> ServeStats:
        """Graceful drain: stop admitting, flush everything admitted,
        tear down, return the final stats. Idempotent. Health goes
        DRAINING immediately — a healthz poller (the fleet router's
        scrape) sees it before the flush completes, which is the point:
        stop routing here NOW (the SIGTERM → exit-75 contract)."""
        self.health.draining()
        self._draining.set()
        self._queue.close()  # also clears any pause: drain must finish
        if self._thread.is_alive():
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise TimeoutError(
                    f"dispatcher did not drain within {timeout}s "
                    f"({len(self._queue)} requests still queued)"
                )
        if not self._drained:
            self._drained = True
            self._throttle.drain()
            try:
                self._drainer.close()
            except Exception as e:
                # The drain worker died with batches in flight: their
                # clients get explicit `error` responses and the failure
                # is accounted — drain still returns the final stats
                # (nothing admitted is ever silently lost).
                import sys

                print(f"serve drain worker failed: {e!r}", file=sys.stderr)
                self._fail_inflight(e)
        return self.stats

    def report(self) -> dict:
        """One JSON-able summary: stats + budget + executable accounting.

        Every pre-telemetry key survives verbatim (back-compat pinned in
        tests/test_observability.py); ``stages`` adds the per-stage
        p50/p99 latency breakdown from the span tracer alongside.
        """
        stages = {
            k: v
            for k, v in self._tel.tracer.stage_summary().items()
            if k.startswith("serve_")
        }
        return {
            "stats": self.stats.summary(),
            "budget": self.budget.summary(),
            "budget_drops": self.budget.drops,
            "budget_recoveries": self.budget.recoveries,
            "budget_slo_drops": self.budget.slo_drops,
            "budget_expected_iters": round(
                self.budget.expected_iters, 3
            ),
            "executables": dict(self._fwd.stats),
            "precision": self._fwd.policy.name,  # RESOLVED (None inherits)
            "mesh": self._fwd.mesh_fp,
            "stages": stages,
            "health": self.health.snapshot(),
            "startup": startup_report(),
        }

    def __enter__(self) -> "FlowServer":
        return self

    def __exit__(self, *exc) -> None:
        self.drain()
