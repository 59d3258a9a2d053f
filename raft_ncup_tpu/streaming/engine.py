"""The multi-stream video engine: device-resident warm start over a
fixed-capacity slot table, with per-stream fault isolation.

Data path (one dispatcher thread; clients submit from their own
threads):

1. **stream admission** (client thread, inside ``submit``): an unknown
   ``stream_id`` claims the lowest free slot; a full table first evicts
   idle-expired streams, then sheds with an honest ``retry_after_s``
   (time until the soonest slot becomes reclaimable). Slots are a HARD
   capacity — a stream without a slot cannot make progress, so stream
   overload sheds instead of queueing (``serving/admission.py``'s
   discipline lifted from requests to streams).
2. **frame admission**: metadata validation (shape/dtype, padded shape
   must equal the engine's slot-table shape, per-stream frame indices
   strictly increasing), staleness decision (index gap >
   ``max_frame_gap`` ⇒ this frame is forced COLD — a stale warm start
   is worse than none), then a non-blocking ``AdmissionQueue.offer``.
   Any /8 frame shape up to UHD (2176x3840) is a valid engine shape:
   the banded corr tier keeps the 4K per-level lookup on-kernel and
   the onthefly fallback bounds the working set, so a 4K slot table
   warms like any other (docs/PERF.md "Banded dispatch").
3. **assemble** (dispatcher): ``pop_batch(..., distinct_fn=stream)``
   pops a FIFO run of frames from DISTINCT streams — two frames of one
   stream must be chained through the slot table, never batched
   together — and zero-pads rows up to the nearest allowed batch size;
   pad rows target the scratch slot.
4. **step** (one jitted program per batch size, compiled once): gather
   prev state by slot index → in-graph forward splat
   (``ops/warmstart.forward_interpolate_jax``) masked by the device
   warm flags → batched RAFT forward (optionally seeding the GRU with
   the carried ``net``) → per-row anomaly check (non-finite or
   diverged low-res flow) → scatter the new state back, with anomalous
   rows reset to cold. State never leaves the device between frames.
5. **deliver** (drain worker): the batch's ``(flow_up, bad_flags)``
   ride ONE sanctioned ``jax.device_get`` in the ``AsyncDrain`` worker
   (three spans per batch: ``stream_device_wait``, ``stream_pull``,
   ``stream_deliver``; docs/OBSERVABILITY.md);
   anomalous rows answer ``rejected`` (their stream just went cold),
   healthy rows answer ``ok`` with the unpadded native flow.

Isolation contract (pinned bitwise in tests/test_streaming.py): a
corrupt frame affects exactly one batch row and one slot — batch-mates'
outputs are bitwise identical to an uninjected run (test-mode rows are
batch-independent and every mask is a ``jnp.where`` select, never an
arithmetic blend), and the reset stream's next frame is bitwise a cold
start. Eviction and slot reuse touch no device memory (the new owner's
first frame is forced cold), so the steady-state executable set is
exactly ``len(batch_sizes)`` programs: zero recompiles, zero implicit
host transfers (tests/test_streaming.py pins both).

Drain contract: ``drain()`` stops stream and frame admission, flushes
every admitted frame through compute, tears down, and returns the final
stats — nothing admitted is silently lost (``serve.py --stream`` wires
it to SIGTERM via ``resilience/preemption.PreemptionHandler`` ⇒ exit
75).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Tuple

import numpy as np

from raft_ncup_tpu.config import StreamConfig
from raft_ncup_tpu.inference.pipeline import (
    AsyncDrain,
    DispatchThrottle,
    ShapeCachedForward,
)
from raft_ncup_tpu.observability import (
    StartupPhase,
    get_startup_record,
    get_telemetry,
    startup_report,
)
from raft_ncup_tpu.observability.telemetry import LEGACY_KEY_ALIASES
from raft_ncup_tpu.ops.padding import InputPadder
from raft_ncup_tpu.serving.admission import AdmissionQueue
from raft_ncup_tpu.serving.request import (
    STATUS_ERROR,
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHED,
    FlowResponse,
    ServeHandle,
)
from raft_ncup_tpu.streaming.slots import SlotRegistry, init_slot_table

_POLL_S = 0.05  # dispatcher wake cadence while the queue is idle


@dataclass
class FrameRequest:
    """One admitted frame of one stream, queued for dispatch."""

    request_id: int
    stream_id: str
    slot: int
    frame_index: int
    image1: np.ndarray
    image2: np.ndarray
    cold: bool  # forced cold start (first frame / gap > max_frame_gap)
    submit_time: float
    pad_spec: tuple
    shape_key: Tuple[int, int]  # padded (H, W): AdmissionQueue's key_fn
    # Cross-process trace id adopted from an inbound TraceContext (the
    # fleet router's wire header); rides this frame's spans so one
    # trace_id spans the router hop (observability/spans.py).
    trace_id: Optional[str] = None


@dataclass(eq=False)
class StreamStats:
    """Per-run streaming accounting (ServeStats' note_*-only discipline:
    submit callers, the dispatcher, and the drain worker all write).
    Each ``note`` mirrors into the telemetry registry under the
    canonical counter name (``LEGACY_KEY_ALIASES["stream"]``); the
    legacy summary keys never change."""

    submitted: int = 0
    accepted: int = 0
    completed: int = 0
    shed_streams: int = 0  # stream admission refused (table full)
    shed_frames: int = 0  # frame admission refused (queue full/draining)
    rejected: int = 0  # malformed frames (admission-time validation)
    resets: int = 0  # in-graph anomaly cold-start resets delivered
    errors: int = 0
    batches: int = 0
    padded_rows: int = 0
    streams_opened: int = 0
    streams_closed: int = 0
    streams_evicted: int = 0
    cold_starts: int = 0  # frames dispatched cold (first/gap/reset-next)
    telemetry: object = field(default=None, repr=False, compare=False)
    _lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False
    )

    def note(self, field_name: str, delta: int = 1) -> None:
        with self._lock:
            setattr(self, field_name, getattr(self, field_name) + delta)
        if self.telemetry is not None and delta:
            self.telemetry.inc(
                LEGACY_KEY_ALIASES["stream"][field_name], delta
            )

    def summary(self) -> str:
        return (
            f"submitted={self.submitted} accepted={self.accepted} "
            f"completed={self.completed} shed_streams={self.shed_streams} "
            f"shed_frames={self.shed_frames} rejected={self.rejected} "
            f"resets={self.resets} errors={self.errors} "
            f"batches={self.batches} padded_rows={self.padded_rows} "
            f"opened={self.streams_opened} closed={self.streams_closed} "
            f"evicted={self.streams_evicted} cold_starts={self.cold_starts}"
        )


class StreamEngine:
    """Serve many concurrent video streams against one model + variables.

    ``clock`` is injectable (tests drive idle eviction and chaos
    schedules deterministically); it must be monotonic. The engine owns
    one dispatcher thread from construction until :meth:`drain`.
    """

    def __init__(
        self,
        model,
        variables: dict,
        cfg: Optional[StreamConfig] = None,
        *,
        mesh=None,
        clock: Callable[[], float] = time.monotonic,
        telemetry=None,
    ):
        self.cfg = cfg or StreamConfig()
        self._clock = clock
        # Telemetry hub (observability/): counters mirror under the
        # canonical names, slot lifecycle (admit/evict/shed/reset) lands
        # as correlated ring events, spans trace each batch's stages.
        self._tel = telemetry if telemetry is not None else get_telemetry()
        self.stats = StreamStats(telemetry=self._tel)
        # Machine-readable health (observability/health.py): STARTING →
        # WARMING/READY through warmup (or first batch), READY ⇄
        # DEGRADED via the hub's SLO verdicts, DRAINING in drain() —
        # the stream half of the serve.py --healthz_file surface.
        self.health = self._tel.health("stream", fresh=True)
        # Mesh-first streaming (docs/SHARDING.md): an explicit `mesh=`
        # wins; otherwise StreamConfig.mesh = (data, spatial) builds
        # one. The step programs then compile as SPMD — frame batches
        # sharded over `data`, frame height over `spatial`, the slot
        # table over `data` (when capacity+1 divides it) — and frames
        # pad to the mesh divisor.
        from raft_ncup_tpu.parallel.mesh import resolve_config_mesh

        mesh, self._pad_divisor = resolve_config_mesh(mesh, self.cfg.mesh)
        self.mesh = mesh
        h, w = self.cfg.frame_hw
        padder = InputPadder(
            (int(h), int(w), 3), mode="sintel",
            divisor=self._pad_divisor, bucket=self.cfg.pad_bucket,
        )
        (t, b), (le, r) = padder.pad_spec
        self._ph, self._pw = int(h) + t + b, int(w) + le + r
        self._h8, self._w8 = self._ph // 8, self._pw // 8
        self._hidden = (
            model.cfg.hidden_dim if self.cfg.carry_net else 0
        )
        # Per-engine precision policy (docs/PRECISION.md): the step
        # programs compile under it and the slot table's recurrent state
        # is STORED at its state dtype (bf16 presets halve per-stream
        # HBM; the step upcasts to the pinned f32 coord dtype before the
        # splat). None inherits the model's own policy.
        from raft_ncup_tpu.precision import resolve_policy

        self._policy = (
            resolve_policy(self.cfg.precision)
            if self.cfg.precision is not None
            else resolve_policy(getattr(model, "policy", None))
        )
        # The device slot table. Owned by the dispatcher thread after
        # construction: every step call donates it and replaces the
        # reference with the program's output, so exactly one live copy
        # exists in HBM.
        self._table = init_slot_table(
            self.cfg.capacity, self._h8, self._w8, self._hidden,
            dtype=self._policy.state_jnp,
        )
        # Serializes every step invocation that donates the table: the
        # dispatcher owns it in steady state, but warmup() also runs
        # step programs — two concurrent donors of the same buffer
        # would be a use-after-donate.
        self._table_lock = threading.Lock()
        self._fwd = ShapeCachedForward(
            model, variables, mesh=mesh, cache_size=self.cfg.cache_size,
            policy=self._policy, telemetry=self._tel,
        )
        self._queue = AdmissionQueue(
            self.cfg.queue_capacity, telemetry=self._tel, name="stream"
        )
        self._throttle = DispatchThrottle(self.cfg.inflight)
        self._drainer = AsyncDrain(depth=self.cfg.drain_depth)
        self.registry = SlotRegistry(self.cfg.capacity)
        self._reg_lock = threading.Lock()
        self._handles: dict[int, ServeHandle] = {}
        self._inflight: dict[int, list] = {}  # drain-failure safety net
        self._inflight_seq = 0
        self._inflight_lock = threading.Lock()
        self._service_ema: Optional[float] = None
        self._ema_lock = threading.Lock()
        self._next_id = 0
        self._id_lock = threading.Lock()
        self.warmed: list = []  # (ph, pw, batch, iters) set, see warmup()
        self._occupancy_sum = 0  # sampled at each dispatched batch
        self._draining = threading.Event()
        self._drained = False
        self._thread = threading.Thread(
            target=self._dispatch_loop, name="stream-dispatch", daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------------ admission

    def submit(
        self,
        stream_id: str,
        image1,
        image2,
        *,
        frame_index: Optional[int] = None,
        request_id: Optional[int] = None,
        trace_id: Optional[str] = None,
    ) -> ServeHandle:
        """Submit the next frame pair of ``stream_id``; returns a handle.

        An unknown stream id is admitted on first use (slot allocation,
        possibly shedding). ``frame_index`` defaults to
        last-admitted + 1; explicit indices must be strictly increasing
        per stream, and a gap beyond ``max_frame_gap`` forces a cold
        start (stale warm state is never used). ``request_id`` lets a
        fleet router supply its correlation id as the frame's identity
        (docs/FLEET.md; caller owns uniqueness); ``trace_id`` adopts the
        router's inbound trace context onto this frame's spans.
        """
        self.stats.note("submitted")
        handle = ServeHandle()
        if request_id is not None:
            rid = int(request_id)
        else:
            with self._id_lock:
                rid = self._next_id
                self._next_id += 1
        if self._draining.is_set():
            self.stats.note("shed_frames")
            handle.complete(FlowResponse(
                rid, STATUS_SHED, retry_after_s=self._retry_after(),
                detail="draining",
            ))
            return handle
        err = self._frame_error(image1) or self._frame_error(image2)
        if err is None and image1.shape != image2.shape:
            err = f"frame shapes differ: {image1.shape} vs {image2.shape}"
        if err is not None:
            self.stats.note("rejected")
            handle.complete(FlowResponse(rid, STATUS_REJECTED, detail=err))
            return handle

        now = self._clock()
        native_hw = (int(image1.shape[0]), int(image1.shape[1]))
        with self._reg_lock:
            state = self.registry.get(stream_id)
            if state is None:
                evicted = self.registry.evict_expired(
                    now, self.cfg.idle_timeout_s
                )
                for s in evicted:
                    self.stats.note("streams_evicted")
                    self._tel.event(
                        "stream_slot_evicted",
                        stream_id=s.stream_id, slot=s.slot,
                    )
                state = self.registry.admit(stream_id, native_hw, now)
                if state is None:
                    self.stats.note("shed_streams")
                    self._tel.event(
                        "stream_slot_shed", stream_id=stream_id
                    )
                    hint = self.registry.soonest_expiry_s(
                        now, self.cfg.idle_timeout_s
                    )
                    handle.complete(FlowResponse(
                        rid, STATUS_SHED,
                        retry_after_s=round(hint, 4),
                        detail="stream table full",
                    ))
                    return handle
                self.stats.note("streams_opened")
                self._tel.event(
                    "stream_slot_admitted",
                    stream_id=stream_id, slot=state.slot,
                )
            if state.native_hw != native_hw:
                self.stats.note("rejected")
                handle.complete(FlowResponse(
                    rid, STATUS_REJECTED,
                    detail=(
                        f"stream {stream_id!r} is {state.native_hw}, "
                        f"got frame {native_hw}"
                    ),
                ))
                return handle
            if state.closing:
                self.stats.note("shed_frames")
                handle.complete(FlowResponse(
                    rid, STATUS_SHED, detail="stream closing",
                ))
                return handle
            last = state.last_frame_index
            idx = frame_index if frame_index is not None else (
                0 if last is None else last + 1
            )
            if last is not None and idx <= last:
                self.stats.note("rejected")
                handle.complete(FlowResponse(
                    rid, STATUS_REJECTED,
                    detail=(
                        f"out-of-order frame index {idx} (last admitted "
                        f"{last}) for stream {stream_id!r}"
                    ),
                ))
                return handle
            cold = last is None or (idx - last) > self.cfg.max_frame_gap
            req = FrameRequest(
                request_id=rid,
                stream_id=stream_id,
                slot=state.slot,
                frame_index=idx,
                image1=image1,
                image2=image2,
                cold=cold,
                submit_time=now,
                pad_spec=self._pad_spec_for(native_hw),
                shape_key=(self._ph, self._pw),
                trace_id=None if trace_id is None else str(trace_id),
            )
            self._handles[rid] = handle
            if not self._queue.offer(req):
                self._handles.pop(rid, None)
                self.stats.note("shed_frames")
                handle.complete(FlowResponse(
                    rid, STATUS_SHED, retry_after_s=self._retry_after(),
                    detail="frame queue full",
                ))
                return handle
            # Admission bookkeeping only after the offer sticks: a shed
            # frame must not advance the stream's index or keep it warm.
            state.last_frame_index = idx
            state.last_activity = now
            state.pending += 1
            state.frames_admitted += 1
        if cold:
            self.stats.note("cold_starts")
        self.stats.note("accepted")
        return handle

    def close_stream(self, stream_id: str) -> bool:
        """Stop admitting frames for ``stream_id``; its slot frees once
        everything already admitted has been answered. Returns False for
        an unknown stream."""
        with self._reg_lock:
            state = self.registry.get(stream_id)
            if state is None:
                return False
            state.closing = True
            if state.pending == 0:
                slot = self.registry.release(stream_id)
                self.stats.note("streams_closed")
                self._tel.event(
                    "stream_slot_released", stream_id=stream_id, slot=slot
                )
        return True

    def _frame_error(self, image) -> Optional[str]:
        shape = getattr(image, "shape", None)
        dtype = getattr(image, "dtype", None)
        if shape is None or dtype is None:
            return f"not an array: {type(image).__name__}"
        if len(shape) != 3 or shape[-1] != 3:
            return f"want (H, W, 3), got shape {tuple(shape)}"
        if np.dtype(dtype).kind not in "uif":
            return f"non-numeric dtype {dtype}"
        h, w = int(shape[0]), int(shape[1])
        padder = InputPadder(
            (h, w, 3), mode="sintel", divisor=self._pad_divisor,
            bucket=self.cfg.pad_bucket,
        )
        (t, b), (le, r) = padder.pad_spec
        if (h + t + b, w + le + r) != (self._ph, self._pw):
            return (
                f"frame {h}x{w} pads to {(h + t + b, w + le + r)}, but "
                f"this engine serves the {(self._ph, self._pw)} slot "
                "table (one padded shape per engine)"
            )
        return None

    def _pad_spec_for(self, native_hw: Tuple[int, int]) -> tuple:
        h, w = native_hw
        return InputPadder(
            (h, w, 3), mode="sintel", divisor=self._pad_divisor,
            bucket=self.cfg.pad_bucket,
        ).pad_spec

    def _retry_after(self) -> float:
        with self._ema_lock:
            per_frame = self._service_ema
        if per_frame is None:
            return self.cfg.default_retry_after_s
        return round((len(self._queue) + 1) * per_frame, 4)

    # ------------------------------------------------------------- dispatch

    def _dispatch_loop(self) -> None:
        while True:
            # The wait for frames and the distinct-stream scan: one span
            # per assembled batch (an idle poll leaves none).
            with self._tel.span("stream_batch_assembly") as sp:
                batch = self._queue.pop_batch(
                    self.cfg.max_batch,
                    timeout=_POLL_S,
                    distinct_fn=lambda r: r.stream_id,
                )
                if batch:
                    sp.set(batch_size=len(batch))
                else:
                    sp.discard()
            if not batch:
                if self._queue.closed and not len(self._queue):
                    return
                # Idle tick: abandoned streams lose their slots even
                # when no new admission forces the scan.
                with self._reg_lock:
                    evicted = self.registry.evict_expired(
                        self._clock(), self.cfg.idle_timeout_s
                    )
                for s in evicted:
                    self.stats.note("streams_evicted")
                    self._tel.event(
                        "stream_slot_evicted",
                        stream_id=s.stream_id, slot=s.slot,
                    )
                continue
            try:
                self._process(batch)
            except BaseException as e:  # noqa: BLE001 — per-frame status
                # Server-side fault (XLA error, drain-worker failure):
                # every still-pending frame in this batch answers
                # `error`; stranded in-flight batches are flushed from
                # the registry (AsyncDrain surfaces worker errors on a
                # LATER submit). The engine keeps serving.
                self._fail_inflight(e)
                for req in batch:
                    if self._complete(req.request_id, FlowResponse(
                        req.request_id, STATUS_ERROR, detail=repr(e),
                    )):
                        self._finish_frame(req)
                        self.stats.note("errors")

    def _step(self, n_rows: int):
        """The compiled slot-table step for one batch size (compiled
        once per size; ``ShapeCachedForward.custom`` accounts it)."""
        cfg = self.cfg
        # The policy-resolved model: the engine's forward computes at
        # the engine policy's dtypes regardless of which preset the
        # caller's model instance was built under.
        model, policy = self._fwd.model_for()

        def build():
            import jax
            import jax.numpy as jnp

            from raft_ncup_tpu.ops.warmstart import (
                forward_interpolate_batch,
            )

            iters, thresh = cfg.iters, cfg.anomaly_max_flow
            carry_net = bool(self._hidden)
            state_dt = policy.state_jnp
            mesh = self.mesh

            def fn(v, table, img1, img2, slot_idx, cold):
                # Storage is (possibly) narrow; the warm-start splat is
                # coordinate arithmetic, so it runs at the policy's
                # pinned f32 coord dtype. jax.named_scope labels the
                # step's stages in the compiled HLO for xprof
                # (docs/OBSERVABILITY.md).
                with jax.named_scope("stream.slot_gather"):
                    prev_flow = table["flow"][slot_idx].astype(
                        policy.coord_jnp
                    )  # (B, h8, w8, 2)
                    warm = (
                        table["warm"][slot_idx] * (1.0 - cold) > 0.5
                    )  # (B,) bool
                with jax.named_scope("stream.warmstart_splat"):
                    splat = forward_interpolate_batch(
                        prev_flow, cfg.splat_chunk
                    )
                    finit = jnp.where(
                        warm[:, None, None, None], splat,
                        jnp.zeros_like(splat),
                    )
                kwargs = {}
                if carry_net:
                    kwargs = {
                        "net_init": table["net"][slot_idx],
                        "net_warm": warm,
                    }
                flow_lr, flow_up, net_f = model.apply(
                    v, img1, img2, iters=iters, flow_init=finit,
                    test_mode=True, return_net=True, mesh=mesh, **kwargs,
                )
                # In-graph anomaly: a non-finite or diverged row resets
                # ITS slot to cold; batch-mates' rows are untouched.
                with jax.named_scope("stream.anomaly_scatter"):
                    bad = (
                        ~jnp.isfinite(flow_lr).all(axis=(1, 2, 3))
                        | ~jnp.isfinite(flow_up).all(axis=(1, 2, 3))
                        | (jnp.abs(flow_lr).max(axis=(1, 2, 3)) > thresh)
                    )
                    good = ~bad
                    gm = good[:, None, None, None]
                    new_table = dict(table)
                    # Scatter back at the table's STORAGE dtype (donation
                    # needs matching avals; bf16 presets narrow here).
                    new_flow = jnp.where(
                        gm, flow_lr, jnp.zeros_like(flow_lr)
                    ).astype(state_dt)
                    new_table["flow"] = table["flow"].at[slot_idx].set(
                        new_flow
                    )
                    new_table["warm"] = table["warm"].at[slot_idx].set(
                        good.astype(table["warm"].dtype)
                    )
                    if carry_net:
                        netf = net_f.astype(state_dt)
                        new_table["net"] = table["net"].at[slot_idx].set(
                            jnp.where(gm, netf, jnp.zeros_like(netf))
                        )
                return new_table, flow_up, bad

            # Donate the slot table: the step's scatter updates it in
            # place, so exactly one table lives in HBM.
            if mesh is None:
                return jax.jit(fn, donate_argnums=(1,))
            # SPMD step (docs/SHARDING.md): one program over the whole
            # mesh — frame batches shard over (data, spatial), the slot
            # table over `data` when its capacity+1 rows divide the
            # axis (else replicated: uneven NamedShardings are a jit
            # error, and the table is small next to the activations).
            # Donation still holds: in/out table shardings match.
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(mesh, P())
            img = NamedSharding(mesh, P("data", "spatial"))
            n_data = int(mesh.shape.get("data", 1))
            tab = (
                NamedSharding(mesh, P("data"))
                if (cfg.capacity + 1) % n_data == 0
                else repl
            )
            table_sh = {"flow": tab, "warm": tab}
            if carry_net:
                table_sh["net"] = tab
            return jax.jit(
                fn,
                in_shardings=(repl, table_sh, img, img, repl, repl),
                out_shardings=(table_sh, repl, repl),
                donate_argnums=(1,),
            )

        return self._fwd.custom(
            ("stream", n_rows, policy.fingerprint()), build
        )

    def _process(self, batch: list) -> None:
        import jax.numpy as jnp

        # Batch correlation id, minted up front so every span/event of
        # this batch carries it (doubles as the in-flight token).
        with self._inflight_lock:
            token = self._inflight_seq
            self._inflight_seq += 1
        now = self._clock()
        for req in batch:
            self._tel.observe_ms(
                "stream_queue_wait", (now - req.submit_time) * 1e3,
                request_id=req.request_id, stream_id=req.stream_id,
                batch_id=token,
                **({"trace_id": req.trace_id}
                   if req.trace_id is not None else {}),
            )
        # First assembly of an engine that never warmed up: serving ⇒
        # READY (guarded so an SLO-driven DEGRADED is not undone here).
        if self.health.state in ("starting", "warming"):
            self.health.ready("serving")
        n_rows = next(
            b for b in self.cfg.batch_sizes if b >= len(batch)
        )
        pad_rows = n_rows - len(batch)
        with self._tel.span(
            "stream_pad_stage", batch_id=token, rows=len(batch),
            pad_rows=pad_rows,
        ):
            rows1 = [self._stage(r.image1, r.pad_spec) for r in batch]
            rows2 = [self._stage(r.image2, r.pad_spec) for r in batch]
            slot_idx = [r.slot for r in batch]
            cold = [1.0 if r.cold else 0.0 for r in batch]
            scratch = self.cfg.capacity
            for _ in range(pad_rows):
                rows1.append(
                    np.zeros((self._ph, self._pw, 3), np.float32)
                )
                rows2.append(
                    np.zeros((self._ph, self._pw, 3), np.float32)
                )
                slot_idx.append(scratch)
                cold.append(1.0)
        self.stats.note("batches")
        self.stats.note("padded_rows", pad_rows)
        with self._reg_lock:
            self._occupancy_sum += self.registry.occupancy
            self._tel.gauge_set(
                "stream_slot_occupancy", self.registry.occupancy
            )

        t_dispatch = self._clock()
        step = self._step(n_rows)
        # As in FlowServer: the dispatch span times the host-to-device
        # copy and the jit dispatch alone; the throttle's bounded wait
        # has its own span, and the drain worker's three (device wait,
        # pull, deliver) follow under the same batch id.
        trace_ids = [r.trace_id for r in batch if r.trace_id is not None]
        with self._tel.span(
            "stream_dispatch",
            batch_id=token,
            request_ids=[r.request_id for r in batch],
            stream_ids=[r.stream_id for r in batch],
            mesh=self._fwd.mesh_fp,
            policy=self._policy.name,
            **({"trace_ids": trace_ids} if trace_ids else {}),
        ):
            with self._table_lock:
                self._table, flow_up, bad = step(
                    self._fwd.variables,
                    self._table,
                    jnp.asarray(np.stack(rows1)),
                    jnp.asarray(np.stack(rows2)),
                    jnp.asarray(np.asarray(slot_idx, np.int32)),
                    jnp.asarray(np.asarray(cold, np.float32)),
                )
        with self._tel.span("stream_throttle_wait", batch_id=token):
            self._throttle.push(flow_up)
        with self._inflight_lock:
            self._inflight[token] = batch

        def deliver(host, batch=batch, token=token):
            with self._inflight_lock:
                self._inflight.pop(token, None)
            host_flow, host_bad = host
            done = self._clock()
            # One sanctioned pull per batch (flow + anomaly flags): the
            # independent count flip_recommendations checks against the
            # recorded stream_batches for snapshot consistency.
            self._tel.inc("stream_drain_pulls_total")
            tids = [r.trace_id for r in batch if r.trace_id is not None]
            self._tel.observe_ms(
                "stream_drain", (done - t_dispatch) * 1e3,
                batch_id=token,
                request_ids=[r.request_id for r in batch],
                **({"trace_ids": tids} if tids else {}),
            )
            for k, req in enumerate(batch):
                bad = bool(host_bad[k])
                if bad:
                    resp = FlowResponse(
                        req.request_id, STATUS_REJECTED,
                        latency_s=done - req.submit_time,
                        detail=(
                            "in-graph anomaly: stream reset to cold "
                            "start"
                        ),
                    )
                else:
                    (t, b), (le, r) = req.pad_spec
                    hh, ww = host_flow.shape[1], host_flow.shape[2]
                    resp = FlowResponse(
                        req.request_id, STATUS_OK,
                        flow=host_flow[k, t: hh - b, le: ww - r, :],
                        iters=self.cfg.iters,
                        latency_s=done - req.submit_time,
                    )
                # Gate ALL per-frame bookkeeping on the completion
                # actually happening: if a server-side failure already
                # flushed this frame (_fail_inflight answered it with
                # `error`), finishing it again here would double-
                # decrement the stream's pending count — and a
                # pending==0 misread frees a slot whose stream still
                # has queued frames.
                if not self._complete(req.request_id, resp):
                    continue
                self._finish_frame(req, reset=bad)
                self.stats.note("resets" if bad else "completed")
                if bad:
                    self._tel.event(
                        "stream_anomaly_reset",
                        stream_id=req.stream_id, slot=req.slot,
                        frame_index=req.frame_index, batch_id=token,
                    )
                    # Fault trigger: the reset decision + the recent
                    # timeline (the corrupted frame's whole journey is
                    # still in the ring at delivery time).
                    self._tel.flight_dump(
                        "stream_anomaly_reset",
                        stream_id=req.stream_id, slot=req.slot,
                        frame_index=req.frame_index, batch_id=token,
                    )
                else:
                    # Per-frame end-to-end latency: the SLI behind the
                    # stream_p99_latency SLO (histogram only, no ring
                    # record).
                    self._tel.hist_observe(
                        "stream_e2e_ms",
                        (done - req.submit_time) * 1e3,
                    )
            self._note_service(
                (done - t_dispatch) / max(1, len(batch))
            )

        # The batch's ONE sanctioned pull: full flow + B anomaly flags.
        self._drainer.submit(
            (flow_up, bad), deliver,
            span=lambda stage: self._tel.span(
                "stream_" + stage, batch_id=token
            ),
        )

    def _finish_frame(self, req: FrameRequest, reset: bool = False) -> None:
        """Per-frame terminal bookkeeping: pending counts, deferred
        close-release, activity refresh, reset accounting."""
        with self._reg_lock:
            state = self.registry.get(req.stream_id)
            if state is None:
                return
            state.pending = max(0, state.pending - 1)
            state.frames_completed += 1
            if reset:
                state.resets += 1
            if state.closing and state.pending == 0:
                slot = self.registry.release(req.stream_id)
                self.stats.note("streams_closed")
                self._tel.event(
                    "stream_slot_released",
                    stream_id=req.stream_id, slot=slot,
                )

    def _fail_inflight(self, exc: BaseException) -> None:
        with self._inflight_lock:
            stranded = list(self._inflight.values())
            self._inflight.clear()
        for batch in stranded:
            for req in batch:
                if self._complete(req.request_id, FlowResponse(
                    req.request_id, STATUS_ERROR,
                    detail=f"result drain failed: {exc!r}",
                )):
                    self._finish_frame(req)
                    self.stats.note("errors")

    def _stage(self, image, pad_spec) -> np.ndarray:
        (t, b), (le, r) = pad_spec
        arr = np.asarray(image, np.float32)
        if t or b or le or r:
            arr = np.pad(arr, ((t, b), (le, r), (0, 0)), mode="edge")
        return arr

    def _complete(self, rid: int, response: FlowResponse) -> bool:
        handle = self._handles.pop(rid, None)
        if handle is None:
            return False
        handle.complete(response)
        return True

    def _note_service(self, per_frame_s: float) -> None:
        with self._ema_lock:
            prev = self._service_ema
            self._service_ema = (
                per_frame_s if prev is None
                else 0.8 * prev + 0.2 * per_frame_s
            )
            ema = self._service_ema
        self._tel.gauge_set("stream_service_time_ema_ms", ema * 1e3)

    # ------------------------------------------------------------ lifecycle

    def warmup(self) -> int:
        """Compile the whole executable set (one step program per batch
        size) against the scratch slot. Returns programs compiled.
        Pausing the queue keeps NEW batches from assembling; the table
        lock is what makes warmup safe against a batch the dispatcher
        had already popped before the pause landed — both donate the
        slot table, and two concurrent donors of one buffer is a
        use-after-donate."""
        import jax

        self.health.warming()
        before = self._fwd.stats["compiles"]
        self._queue.set_paused(True)
        warmed = []
        try:
            import jax.numpy as jnp

            scratch = self.cfg.capacity
            # The whole warm-up as one start-up phase, parent of the
            # per-executable phases it causes (docs/OBSERVABILITY.md
            # "Start-up timeline").
            with StartupPhase(self._tel, "startup_warmup") as phase:
                for n in self.cfg.batch_sizes:
                    warmed.append((self._ph, self._pw, n, self.cfg.iters))
                    zeros = np.zeros(
                        (n, self._ph, self._pw, 3), np.float32
                    )
                    step = self._step(n)
                    with self._table_lock:
                        self._table, flow_up, bad = step(
                            self._fwd.variables,
                            self._table,
                            jnp.asarray(zeros),
                            jnp.asarray(zeros),
                            jnp.asarray(
                                np.full((n,), scratch, np.int32)
                            ),
                            jnp.asarray(np.ones((n,), np.float32)),
                        )
                    jax.block_until_ready((self._table, flow_up, bad))
                phase.set(
                    programs=self._fwd.stats["compiles"] - before
                )
            get_startup_record().phase("warmup_s", phase.seconds)
        finally:
            self._queue.set_paused(False)
        # The warmed (padded_h, padded_w, batch, iters) step set — the
        # streaming half of the replica identity serve.py threads into
        # healthz (docs/FLEET.md).
        self.warmed = warmed
        compiled = self._fwd.stats["compiles"] - before
        self.health.ready(f"warmup compiled {compiled} programs")
        return compiled

    def pause(self) -> None:
        """Test/ops hook: stop assembling new batches (queued and new
        frames wait). Deterministic, see AdmissionQueue.set_paused."""
        self._queue.set_paused(True)

    def resume(self) -> None:
        self._queue.set_paused(False)

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def drain(self, timeout: Optional[float] = None) -> StreamStats:
        """Graceful drain: stop admitting, flush every admitted frame,
        tear down, return final stats. Idempotent. Health goes DRAINING
        immediately (the SIGTERM → exit-75 contract: a healthz poller
        stops routing streams here before the flush completes)."""
        self.health.draining()
        self._draining.set()
        self._queue.close()  # clears any pause: drain must finish
        if self._thread.is_alive():
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise TimeoutError(
                    f"stream dispatcher did not drain within {timeout}s "
                    f"({len(self._queue)} frames still queued)"
                )
        if not self._drained:
            self._drained = True
            self._throttle.drain()
            try:
                self._drainer.close()
            except Exception as e:
                import sys

                print(
                    f"stream drain worker failed: {e!r}", file=sys.stderr
                )
                self._fail_inflight(e)
        return self.stats

    def executable_memory(self) -> list:
        """XLA's ``memory_analysis()`` of every step program this engine's
        cache compiled, as the cost ledger banked it at compile time."""
        ledger, out = self._fwd.costs, []
        for key in ledger.keys():
            entry = ledger.entry(key) or {}
            is_step = (entry.get("meta") or {}).get("kind") == "stream_step"
            if is_step and entry.get("memory_stats"):
                out.append({"key": key, **entry["memory_stats"]})
        return out

    def report(self) -> dict:
        """One JSON-able summary: stats + slot-table occupancy +
        executable accounting."""
        with self._reg_lock:
            occupancy = self.registry.occupancy
            peak = self.registry.peak_occupancy
            evicted = self.registry.evicted_total
        batches = max(1, self.stats.batches)
        # Every pre-telemetry key survives verbatim (back-compat pinned
        # in tests/test_observability.py); `stages` adds the per-stage
        # p50/p99 breakdown from the span tracer alongside.
        stages = {
            k: v
            for k, v in self._tel.tracer.stage_summary().items()
            if k.startswith("stream_")
        }
        return {
            "stats": self.stats.summary(),
            # The same tallies under their canonical counter names, as
            # numbers (what a check compares, docs/OBSERVABILITY.md).
            "counters": {
                canon: getattr(self.stats, key)
                for key, canon in LEGACY_KEY_ALIASES["stream"].items()
            },
            "capacity": self.cfg.capacity,
            "occupancy": occupancy,
            "peak_occupancy": peak,
            "mean_occupancy": round(self._occupancy_sum / batches, 2),
            "evicted": evicted,
            "executables": dict(self._fwd.stats),
            "executable_memory": self.executable_memory(),
            "precision": self._policy.name,  # RESOLVED (None inherits)
            "mesh": self._fwd.mesh_fp,
            "stages": stages,
            "health": self.health.snapshot(),
            "startup": startup_report(),
        }

    def __enter__(self) -> "StreamEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.drain()
