"""Asynchronous inference/eval pipeline: decode-ahead, double-buffered
device staging, bounded shape-cached executables, and a non-blocking
device→host drain.

The eval loop's steady state mirrors the train loop's (docs/PERF.md):

- **decode ahead** (:class:`SamplePrefetcher`): a thread pool decodes
  dataset samples ``lookahead`` frames ahead of consumption, order
  preserved, with the same close/exception contract as
  ``data/device_prefetch.DevicePrefetcher`` — worker errors re-raise
  from the consumer's ``next()`` and ``close()`` cancels pending work
  (the old ``_prefetch_samples`` generator silently blocked on pool
  shutdown when abandoned mid-validation and never surfaced decode
  errors until ``.result()``).
- **stage + transfer ahead** (:class:`EvalPipeline`): host batching /
  padding runs on the DevicePrefetcher's worker thread and the staged
  batch moves to device ``depth`` batches ahead of compute — the
  consumer's ``next()`` returns device-resident arrays.
- **compute** (:class:`ShapeCachedForward`): one compiled executable per
  (padded shape with the batch, native shape, iters, metric kind),
  bounded by an LRU. A validation pass groups its samples by native size
  across the stream (:func:`uniform_batches`), so a pass over KITTI's
  mixed sizes runs one executable a size; pad bucketing
  (``ops/padding.InputPadder(bucket=...)``) is the separate, optional
  collapse of the PADDED shapes. The metric variant folds
  ``inference/metrics.py`` into the SAME jitted program as the forward
  (``RAFT.apply(metric_head=...)``), so validation never materializes a
  full flow field on host.
- **drain** (:class:`AsyncDrain`): submissions still need full-field
  pulls; they happen on a worker thread behind dispatch — the window
  boundary's sanctioned ``jax.device_get``, moved off the hot loop.
- **bounded dispatch** (:class:`DispatchThrottle`): the number of
  in-flight compiled programs is capped per backend (1 on CPU, where
  queued programs execute concurrently on the shared host pool and
  destroy each other's intra-op parallelism; 2 on accelerators, whose
  serialized stream just wants to stay fed across dispatch gaps).

Run the whole loop under ``analysis/guards.py``
(``forbid_host_transfers`` + ``RecompileWatchdog``) and it inherits the
train loop's invariants: zero implicit host pulls, zero steady-state
recompiles (tests/test_inference_pipeline.py pins both).
"""

from __future__ import annotations

import queue
import sys
import threading
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from raft_ncup_tpu.data.device_prefetch import DevicePrefetcher
from raft_ncup_tpu.inference import metrics as metrics_mod
from raft_ncup_tpu.inference.costs import (
    build_and_record,
    first_run,
    get_cost_ledger,
)
from raft_ncup_tpu.observability import NOOP_SPAN, get_telemetry
from raft_ncup_tpu.observability.telemetry import LEGACY_KEY_ALIASES
from raft_ncup_tpu.precision import resolve_policy
from raft_ncup_tpu.precision.sites import summarize_sites
from raft_ncup_tpu.utils.profiling import annotate_spans, compile_meter

_EXEC_CANON = LEGACY_KEY_ALIASES["inference"]

# FRAME_DTYPE: what ``RAFT.apply`` takes its frames as, float32 in
# [0, 255], under every PrecisionPolicy preset: the model normalises them
# first and the policy's compute cast comes after (models/raft.py), so no
# preset names this dtype and none narrows it. A metrics program widens
# the frames a pass staged narrower (uint8 rows) to it; for float32 frames
# the cast traces to nothing.
FRAME_DTYPE = jnp.float32


def env_earlyexit_tol() -> Optional[float]:
    """Resolve the early-exit knobs (utils/knobs.py; docs/PERF.md "Early
    exit") to a tolerance, or None when detection is off. This is THE
    env chokepoint for early exit: the model layer takes an explicit
    ``early_exit_tol`` argument and never reads the environment, so
    compiled-program identity stays a pure function of call arguments.
    """
    from raft_ncup_tpu.utils.knobs import knob_flag, knob_float

    if not knob_flag("RAFT_NCUP_EARLYEXIT"):
        return None
    return knob_float("RAFT_NCUP_EARLYEXIT_TOL")


class SamplePrefetcher:
    """Decode dataset samples ahead of consumption, order-preserving.

    Contracts (aligned with ``DevicePrefetcher``):

    - order: samples come out exactly as ``dataset.sample(0..n-1)``;
    - exceptions: a decode error re-raises from the consumer's
      ``next()`` (after closing the pool);
    - close: cancels queued decodes and joins the pool; idempotent;
      called automatically on exhaustion and by the context manager, so
      an early-exiting consumer leaks no threads.
    """

    def __init__(self, dataset, num_workers: int = 4, lookahead: int = 8):
        self._ds = dataset
        self._n = len(dataset)
        self._pool = ThreadPoolExecutor(
            max(1, num_workers), thread_name_prefix="eval-decode"
        )
        self._futures: deque = deque()
        self._submitted = 0
        self._closed = False
        for _ in range(min(max(1, lookahead), self._n)):
            self._submit_next()

    def _submit_next(self) -> None:
        self._futures.append(
            self._pool.submit(self._ds.sample, self._submitted)
        )
        self._submitted += 1

    def __iter__(self) -> Iterator[dict]:
        return self

    def __next__(self) -> dict:
        if self._closed or not self._futures:
            self.close()
            raise StopIteration
        fut = self._futures.popleft()
        try:
            sample = fut.result()
        except BaseException:
            self.close()
            raise
        if self._submitted < self._n:
            self._submit_next()
        return sample

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for fut in self._futures:
            fut.cancel()
        self._futures.clear()
        # Queued work is cancelled above, so the join only waits for
        # decodes already in flight — bounded, not a full-epoch drain.
        self._pool.shutdown(wait=True, cancel_futures=True)

    def __enter__(self) -> "SamplePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def uniform_batches(
    samples: Iterable[dict], batch_size: int, fill_valid: bool = False
) -> Iterator[list]:
    """Group a sample stream into same-size batches, BY NATIVE SIZE ACROSS
    THE STREAM: one pending list a size, a group emitted when a list holds
    ``batch_size``, the remainders at the stream's end in the order of
    their shapes (not of arrival). Pending samples never exceed sizes x
    (``batch_size`` - 1). A stream of one size (Sintel, chairs) comes out
    as it always did: full groups in arrival order and one short last
    group. Batching amortizes dispatch and fills the MXU; the reference
    evaluates strictly frame-by-frame (evaluate.py:98-104), and every
    metric here is a sum that commutes, so the order of dispatch changes
    no answer.

    ``fill_valid`` (the kinds whose batch carries a ``valid`` mask:
    KITTI) also emits each remainder as a FULL group: the missing rows
    are fill rows, the group's last real sample again with its ``valid``
    all zero and the key ``"fill"`` set. The metric head counts a row
    without a valid pixel as no frame (inference/metrics.py), so the sums
    are those of the real samples alone, and every group of a size has
    one shape: the executables a pass needs are one a native size,
    whatever the order of arrival and whatever the remainders (groups cut
    at every change of size would need one a (size, length), and
    KITTI-2015's four sizes in no order then overflow the executable
    cache). Without it a remainder stays short: a kind without a mask has
    no operand that could mark a fill row.
    """
    pending: dict = {}
    for s in samples:
        shape = s["image1"].shape
        pending.setdefault(shape, []).append(s)
        if len(pending[shape]) == batch_size:
            yield pending.pop(shape)
    for shape in sorted(pending):
        group = pending[shape]
        if fill_valid:
            last = group[-1]
            fill = {**last, "valid": np.zeros_like(last["valid"]), "fill": True}
            group = group + [fill] * (batch_size - len(group))
        yield group


class EvalPipeline:
    """Double-buffered eval executor: decode → stage → transfer, all off
    the dispatch thread.

    ``stage_fn(group) -> (arrays, meta)`` turns a list of samples into a
    dict of host numpy arrays (stack + pad) plus a small host-side meta
    dict (pad spec, group size). The groups are :func:`uniform_batches`'s:
    by native size across the stream, and with ``fill_valid`` every group
    a full one (fill rows carry the key ``"fill"``). Staging runs inside the
    DevicePrefetcher's worker thread, and the staged arrays are moved to
    device ``depth`` batches ahead — iterating yields
    ``(device_batch, meta)`` pairs whose alignment is guaranteed by the
    single-worker FIFO ordering.

    ``mesh``/``shardings`` forward to the DevicePrefetcher (same
    transfer policy as the train loop): under an SPMD eval mesh the
    worker thread device_puts each batch straight into the compiled
    program's input shardings, so jit dispatch does no re-layout — a
    default-device transfer would be resharded synchronously on the
    dispatch thread at every call, which is exactly the per-batch stall
    this pipeline exists to remove.

    ``telemetry``/``span_attrs`` forward to the DevicePrefetcher too: its
    ``input_stage`` span times this pipeline's decode wait + ``stage_fn``,
    ``input_h2d`` the transfer, ``input_wait`` the consumer's ``next()``.

    Exceptions from decode or staging re-raise from ``next()``;
    ``close()`` (or the context manager) tears down both threads and the
    decode pool even mid-epoch.
    """

    def __init__(
        self,
        dataset,
        stage_fn: Callable[[list], tuple],
        *,
        batch_size: int = 1,
        fill_valid: bool = False,
        depth: int = 2,
        num_workers: int = 4,
        lookahead: Optional[int] = None,
        mesh=None,
        shardings: Optional[dict] = None,
        telemetry=None,
        span_attrs: Optional[dict] = None,
    ):
        self._sp = SamplePrefetcher(
            dataset,
            num_workers,
            lookahead or max(2 * batch_size, num_workers),
        )
        self._meta: deque = deque()
        sp, meta_q = self._sp, self._meta

        def staged():
            try:
                for group in uniform_batches(sp, batch_size, fill_valid):
                    arrays, meta = stage_fn(group)
                    meta_q.append(meta)
                    yield arrays
            finally:
                # DevicePrefetcher closes this generator from its worker
                # thread; propagate that to the decode pool so an
                # abandoned pipeline leaks nothing.
                sp.close()

        self._pf = DevicePrefetcher(
            staged(), depth=depth, mesh=mesh, shardings=shardings,
            drop_keys=(), telemetry=telemetry, span_attrs=span_attrs,
        )

    def __iter__(self) -> Iterator[tuple]:
        return self

    def __next__(self) -> tuple:
        batch = next(self._pf)
        return batch, self._meta.popleft()

    def close(self) -> None:
        self._pf.close()
        self._sp.close()

    def __enter__(self) -> "EvalPipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def default_inflight() -> int:
    """How many dispatched-but-unfinished eval programs to keep in flight.

    On the CPU backend, queued XLA programs execute CONCURRENTLY on the
    shared host thread pool: two in flight halve each other's intra-op
    parallelism and thrash cache (measured ~+8% per pair on a 2-core
    host), so the eval loop keeps exactly ONE in flight and overlaps
    host decode/staging only. Accelerators execute a serialized stream —
    ``inflight=2`` leaves one queued program between pushes, which rides
    out the host's stage/dispatch gap so the device stays fed.
    ``jax.block_until_ready`` on the bounded tail is a sync, not a
    transfer: the loop stays clean under ``forbid_host_transfers``.
    """
    return 1 if jax.default_backend() == "cpu" else 2


class DispatchThrottle:
    """Bound the number of in-flight device computations in a dispatch
    loop (see :func:`default_inflight`). ``push(x)`` registers a freshly
    dispatched output; once ``inflight`` or more are pending it blocks
    until the OLDEST completes, so at most ``inflight`` programs are
    ever in flight and ``inflight - 1`` stay queued between pushes
    (``inflight=1`` ⇒ every push waits for its own program) — bounded
    software pipelining with no host transfer."""

    def __init__(self, inflight: Optional[int] = None):
        self.inflight = inflight if inflight is not None else default_inflight()
        self._pending: deque = deque()

    def push(self, x) -> None:
        self._pending.append(x)
        while len(self._pending) >= max(1, self.inflight):
            jax.block_until_ready(self._pending.popleft())

    def drain(self) -> None:
        while self._pending:
            jax.block_until_ready(self._pending.popleft())


class AsyncDrain:
    """Non-blocking, order-preserving device→host drain.

    ``submit(tree, callback)`` parks a device-array pytree on a bounded
    queue; a worker thread performs the sanctioned ``jax.device_get``
    and hands the host arrays to ``callback``. The dispatch thread never
    blocks on d2h — full-field pulls (submission writers) overlap the
    next frame's compute. A worker error re-raises from the next
    ``submit()`` or from ``close()``; ``close()`` flushes the queue and
    joins. The queue bound (``depth``) also bounds device memory pinned
    by in-flight pulls.

    The worker's three stages are the submitter's to name: ``span`` (a
    ``stage -> context manager``, e.g. ``lambda stage:
    hub.span("serve_" + stage, batch_id=7)``) is entered around
    ``device_wait`` (``jax.block_until_ready`` on the tree: the program
    still running, which the pull would wait for anyway), ``pull`` (the
    ``device_get``: the device-to-host copy alone) and ``deliver`` (the
    callback) — disjoint, in that order, on the worker thread.
    """

    def __init__(self, depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._exc: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._worker, name="eval-drain", daemon=True
        )
        self._thread.start()

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            if self._exc is not None:
                continue  # keep consuming so the producer never deadlocks
            tree, callback, span = item
            try:
                with span("device_wait"):
                    jax.block_until_ready(tree)
                with span("pull"):
                    host = jax.device_get(tree)
                with span("deliver"):
                    callback(host)
            except BaseException as e:  # noqa: BLE001 — surfaced to producer
                self._exc = e

    def _raise_pending(self) -> None:
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def submit(
        self, tree, callback: Callable,
        span: Callable[[str], object] = lambda stage: NOOP_SPAN,
    ) -> None:
        self._raise_pending()
        self._q.put((tree, callback, span))

    def close(self) -> None:
        """Flush remaining work, stop the worker, re-raise its error."""
        if self._thread.is_alive():
            self._q.put(None)
            self._thread.join()
        self._raise_pending()

    def __enter__(self) -> "AsyncDrain":
        return self

    def __exit__(self, et, ev, tb) -> None:
        if et is not None:
            # The body already failed; tear down without masking it.
            try:
                self.close()
            except Exception as e:
                print(f"AsyncDrain close after error: {e}", file=sys.stderr)
            return
        self.close()


class ShapeCachedForward:
    """Bounded LRU of compiled test-mode executables, keyed by (mesh
    fingerprint, padded shape, iters, warm-start presence, metric
    kind/pad, precision-policy fingerprint).

    Frames stream with dataset-dependent sizes, so each unique shape
    compiles once; the LRU bound (default 8, knob:
    ``DataConfig.eval_cache_size``) keeps footage of many sizes (a
    folder of clips, a submission pass, which dispatches pair by pair)
    from growing the cache without limit, and ``stats`` counts
    compiles/hits/evictions so an eviction storm is visible instead of
    silent recompile churn. A validation pass holds the set at one
    executable a native size (:func:`uniform_batches`: KITTI-2015's four
    fit the bound whatever their order); ``InputPadder(bucket=...)``
    makes it smaller still where the sizes outnumber the bound.

    ``policy`` (a :mod:`raft_ncup_tpu.precision` preset name or
    ``PrecisionPolicy``; default = the model's own) selects the dtype
    policy every compiled program runs under; ``forward_device`` /
    ``metrics`` accept a per-call override. The policy fingerprint is
    part of EVERY cache key, so an f32 and a bf16 program for the same
    shape can never collide — same variables (f32 master weights), two
    executables (tests/test_inference_pipeline.py pins this).

    With ``mesh`` set (a (data, spatial) ``jax.sharding.Mesh``) every
    forward is one SPMD program: images sharded over (batch, height),
    variables/metrics replicated — the driver-level entry to
    spatially-sharded high-res eval (models/raft.py).
    """

    def __init__(
        self, model, variables: dict, mesh=None, cache_size: int = 8,
        policy=None, telemetry=None, cost_ledger=None,
    ):
        from raft_ncup_tpu.parallel.mesh import mesh_fingerprint

        self.model = model
        self.variables = variables
        self.mesh = mesh
        # Part of EVERY cache key (see _get): a sharded and an unsharded
        # program for the same shape/iters/policy are different
        # executables, and the fingerprint keeps that distinction even
        # for caches that outlive a mesh reconfiguration (or custom()
        # keys minted by subsystems that never look at self.mesh).
        self.mesh_fp = mesh_fingerprint(mesh)
        # apply()-compatible stand-ins (tests' dummy models) carry no
        # policy; they resolve to the f32 default and are never swapped.
        self.policy = (
            resolve_policy(policy)
            if policy is not None
            else resolve_policy(getattr(model, "policy", None))
        )
        self.cache_size = max(1, int(cache_size))
        self._fns: OrderedDict = OrderedDict()
        self._models_by_policy: dict = {}
        self.stats = {"compiles": 0, "hits": 0, "evictions": 0}
        # Telemetry (observability/): compile/evict land as ring events
        # keyed exactly like the cache (the full executable key string),
        # all three land as canonical counters. Hits are counter-only —
        # one ring event per warm batch would flood the span ring with
        # the steady state the ring exists to contextualize.
        self._tel = telemetry if telemetry is not None else get_telemetry()
        # This cache owns the hub's jax side: its spans (and those of the
        # server or engine that handed the hub in) go on the profiler's
        # timeline too (utils/profiling.annotate_spans).
        annotate_spans(self._tel)
        # The process's compile listener counts from here on (idempotent):
        # the start-up record's process totals, and the cache verdict of
        # every executable this cache builds (utils/profiling.timed_build).
        compile_meter()
        # The executable cost ledger (inference/costs.py; docs/PERF.md):
        # every program this cache compiles is AOT-lowered so its XLA
        # cost analysis, compile wall time, and memory stats land in the
        # ledger at COMPILE time — the warmed hot path pays one dict
        # read. The ledger key embeds the same cache key, so a re-warm
        # (LRU hit) records nothing twice.
        self.costs = (
            cost_ledger if cost_ledger is not None else get_cost_ledger()
        )
        self._backend = jax.default_backend()

    def model_for(self, policy=None):
        """Resolve (model, policy) for one call: the instance model when
        the policy matches its config, else the same-architecture model
        under the requested preset (same f32 master weights). Memoized
        per instance so the serving/streaming dispatch path pays a dict
        lookup, not a config rebuild, per batch."""
        pol = resolve_policy(policy) if policy is not None else self.policy
        own = getattr(self.model, "policy", None)
        if own is None or pol.name == own.name:
            return self.model, pol
        model = self._models_by_policy.get(pol.name)
        if model is None:
            import dataclasses

            from raft_ncup_tpu.models.raft import get_model

            cfg = dataclasses.replace(
                self.model.cfg, precision=pol.name, mixed_precision=False
            )
            model = self._models_by_policy[pol.name] = get_model(cfg)
        return model, pol

    # ------------------------------------------------------------ internals

    def _jit(self, fn, n_img_args: int, n_repl_args: int, n_out: int,
             donate: tuple = ()):
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=donate)
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self.mesh, P())
        img = NamedSharding(self.mesh, P("data", "spatial"))
        return jax.jit(
            fn,
            in_shardings=(repl,) + (img,) * n_img_args + (repl,) * n_repl_args,
            out_shardings=repl if n_out == 1 else (repl,) * n_out,
            donate_argnums=donate,
        )

    @staticmethod
    def _ledger_meta(key: tuple) -> dict:
        """Structured identity for the cost-ledger entry, parsed from
        the raw (pre-mesh-fingerprint) executable key so consumers
        filter on (kind, shape, iters) instead of string-matching keys."""
        if key and isinstance(key[0], tuple):
            # forward key: (shape, iters, warm, policy_fp) — plus an
            # optional trailing ("earlyexit", tol) marker for the
            # convergence-detection twin of a shape (docs/PERF.md "Early
            # exit"): the threshold knob rides the ledger meta exactly
            # like the corr band knobs, so flip_recommendations can
            # attribute an EPE-vs-speedup trade to the tolerance that
            # produced it.
            meta = {"kind": "forward", "shape": key[0], "iters": key[1],
                    "policy": key[3]}
            for part in key[4:]:
                if (
                    isinstance(part, tuple) and len(part) == 2
                    and part[0] == "earlyexit"
                ):
                    meta["earlyexit_tol"] = part[1]
            return meta
        if key and key[0] == "metrics":
            # ("metrics", img_shape, flow_shape, extras, iters, kind,
            #  pad, warm, frame_dtypes, policy_fp) — policy distinguishes
            # the f32 and bf16 twins of one shape (they are different
            # executables with different XLA flops; a meta lookup must
            # not conflate them), ``frames`` the uint8-fed and the
            # float32-fed one.
            return {"kind": "metrics", "shape": key[1], "iters": key[4],
                    "frames": key[8], "policy": key[9]}
        if key and key[0] == "custom":
            if len(key) >= 4 and key[1] == "stream":
                # StreamEngine's slot-table step (streaming/engine.py).
                meta = {"kind": "stream_step", "rows": key[2],
                        "policy": key[3]}
                # Optional trailing ("earlyexit", tol) marker — same
                # contract as the forward key above.
                for part in key[4:]:
                    if (
                        isinstance(part, tuple) and len(part) == 2
                        and part[0] == "earlyexit"
                    ):
                        meta["earlyexit_tol"] = part[1]
                return meta
            return {"kind": "custom"}
        return {}

    def _instrument(self, full_key: tuple, raw_key: tuple, jitfn):
        """Wrap one freshly-built jitted program so its FIRST call
        AOT-compiles (``lower().compile()`` — still exactly one XLA
        compile), banks the executable's costs in the ledger and runs as
        the start-up phase ``startup_first_run`` (``costs.
        build_and_record`` / ``first_run``; docs/OBSERVABILITY.md
        "Start-up timeline");
        every later call is one dict read then the compiled program. Plain
        callables (tests' stand-ins) and a disabled ledger pass through
        untouched."""
        if not self.costs.enabled or not hasattr(jitfn, "lower"):
            return jitfn
        ledger, backend = self.costs, self._backend
        ledger_key = f"{backend}|{full_key}"
        meta = self._ledger_meta(raw_key)
        if meta.get("kind") in ("forward", "metrics"):
            # The correlation tuning knobs the executable was traced
            # with (onthefly row_chunk, Pallas query block / band rows
            # — ops/corr.corr_tuning_meta): the first real sweep
            # surface for ROADMAP item 1's autotuner, persisted next
            # to the XLA cost facts it will optimize against.
            from raft_ncup_tpu.ops.corr import corr_tuning_meta

            meta.update(corr_tuning_meta())
        box: dict = {}
        lock = threading.Lock()
        tel = self._tel

        def warmed(*args):
            compiled = box.get("c")
            if compiled is None:
                with lock:
                    compiled = box.get("c")
                    if compiled is None:
                        try:
                            compiled = build_and_record(
                                ledger, tel, jitfn, args, ledger_key,
                                backend=backend, **meta,
                            )
                        except Exception as e:  # pragma: no cover
                            # Probe unavailable on this backend: serve
                            # through the plain jit wrapper (no ledger
                            # entry — `mfu` stays None, never wrong).
                            print(
                                f"cost probe unavailable for "
                                f"{ledger_key}: {e!r}", file=sys.stderr,
                            )
                            compiled = box["c"] = jitfn
                        else:
                            box["c"] = compiled
                            # shapes alone: what ``lowered_hlo`` lowers again
                            box["avals"] = jax.tree.map(
                                lambda a: jax.ShapeDtypeStruct(
                                    jnp.shape(a), jnp.result_type(a)
                                ),
                                args,
                            )
                            return first_run(
                                ledger, tel, compiled, args, ledger_key,
                                str(meta.get("kind", "custom")),
                            )
            return compiled(*args)

        # Inspection handle: the warmed executable without a second
        # lower().compile(). Empty until the first call. The two others
        # are what ``report`` and ``lowered_hlo`` find the executable by.
        warmed._compiled_box = box
        warmed._jitfn = jitfn
        warmed._ledger_key = ledger_key
        return warmed

    def _get(self, key, build):
        # Single chokepoint for key construction: every compiled-program
        # key — forward, metric, custom — carries the mesh fingerprint.
        raw_key = tuple(key)
        key = (self.mesh_fp,) + raw_key
        fn = self._fns.get(key)
        if fn is not None:
            self._fns.move_to_end(key)
            self.stats["hits"] += 1
            self._tel.inc(_EXEC_CANON["hits"])
            return fn
        fn = self._instrument(key, raw_key, build())
        self._fns[key] = fn
        self.stats["compiles"] += 1
        self._tel.inc(_EXEC_CANON["compiles"])
        self._tel.event("inference_executable_compile", key=str(key))
        if len(self._fns) > self.cache_size:
            evicted, _ = self._fns.popitem(last=False)
            self.stats["evictions"] += 1
            self._tel.inc(_EXEC_CANON["evictions"])
            self._tel.event("inference_executable_evict", key=str(evicted))
            print(
                f"ShapeCachedForward: EVICTING compiled executable "
                f"{evicted} (LRU bound {self.cache_size}). Recurring "
                "evictions mean eval shape churn is re-paying compiles — "
                "raise eval_cache_size or bucket pads (eval_pad_bucket).",
                file=sys.stderr,
            )
        return fn

    # ---------------------------------------------- what a pass reports

    def _newest(self):
        """``(wrapper, ledger entry)`` of the whole-forward executable
        ('metrics' or 'forward') this cache used last, or ``None``."""
        for fn in reversed(list(self._fns.values())):
            entry = self.costs.entry(getattr(fn, "_ledger_key", "")) or {}
            if (entry.get("meta") or {}).get("kind") in ("metrics", "forward"):
                return fn, entry
        return None

    def report(self) -> dict:
        """What an evaluation pass reports of the executable it ran (the
        whole-forward executable this cache used last): the cache's
        compile / hit / eviction counts and ``precision``, from the
        product-site tally banked when the executable was built
        (``precision/sites.py``; docs/OBSERVABILITY.md): the preset in
        the executable's key, how many sites took float32 and how many
        bfloat16 operands, and the sites by path. ``precision`` is
        ``None`` before the first call and where the cost ledger is
        off."""
        found = self._newest()
        precision = None
        if found is not None and found[1].get("product_sites"):
            sites, meta = found[1]["product_sites"], found[1]["meta"]
            counts = summarize_sites(sites, str(meta.get("policy")))
            precision = {"preset": counts.pop("policy"), **counts, "sites": sites}
        return {"executables": dict(self.stats), "precision": precision}

    def lowered_hlo(self) -> Optional[str]:
        """HLO text of the module the compiler was handed for that same
        executable: its jitted function lowered once more on the shapes
        of its first call, under the precision context of the caller
        (the same text on every backend; the executable's own
        ``as_text()`` is what the backend made of it). ``None`` before
        the first call."""
        found = self._newest()
        if found is None or "avals" not in found[0]._compiled_box:
            return None
        fn = found[0]
        lowered = fn._jitfn.lower(*fn._compiled_box["avals"])
        return lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string()

    # ------------------------------------------------------------- forwards

    def custom(self, key: tuple, build):
        """Compile-once entry for subsystem-specific jitted programs that
        want this cache's LRU bound and compiles/hits/evictions
        accounting (the streaming engine's slot-table step programs,
        keyed by batch size). ``build()`` must return the compiled-on-
        first-call callable; ``key`` is namespaced away from the forward
        and metric keys."""
        return self._get(("custom",) + tuple(key), build)

    def forward_device(
        self, image1, image2, iters: int, flow_init=None, policy=None,
        early_exit_tol: Optional[float] = None,
    ):
        """Test-mode forward; returns DEVICE arrays (flow_lr, flow_up).

        The caller owns the pull: submissions hand the result to an
        :class:`AsyncDrain`, the legacy ``__call__`` wraps it in one
        explicit ``jax.device_get``. ``policy`` overrides the instance
        precision policy for this call; the fingerprint in the key keeps
        the override's executable distinct.

        ``early_exit_tol`` (docs/PERF.md "Early exit"): compile the
        convergence-detection variant — the return becomes the 3-tuple
        ``(flow_lr, flow_up, exec_iters)`` with ``exec_iters`` the (B,)
        int32 per-sample executed-iteration count, still device-resident
        (it rides the caller's existing drain/pull; never a second
        sync). The key grows a trailing ``("earlyexit", tol)`` element,
        so detection-off callers keep their exact 4-tuple keys and
        executables — zero churn for existing deployments — while each
        tolerance is its own executable (the tolerance is baked into the
        compiled loop condition).
        """
        model, pol = self.model_for(policy)
        key = (
            tuple(image1.shape), iters, flow_init is not None,
            pol.fingerprint(),
        )
        if early_exit_tol is not None:
            key = key + (("earlyexit", float(early_exit_tol)),)

        def build():
            mesh = self.mesh
            tol = (
                None if early_exit_tol is None else float(early_exit_tol)
            )
            kw = {}
            if tol is not None:
                kw = {"early_exit_tol": tol, "return_exec_iters": True}
            if flow_init is None:

                def fn(v, i1, i2):
                    return model.apply(
                        v, i1, i2, iters=iters, test_mode=True, mesh=mesh,
                        **kw,
                    )

            else:

                def fn(v, i1, i2, finit):
                    return model.apply(
                        v, i1, i2, iters=iters, flow_init=finit,
                        test_mode=True, mesh=mesh, **kw,
                    )

            return self._jit(
                fn, 2 if flow_init is None else 3, 0,
                n_out=2 if early_exit_tol is None else 3,
            )

        args = (jnp.asarray(image1), jnp.asarray(image2))
        if flow_init is not None:
            args += (jnp.asarray(flow_init),)
        return self._get(key, build)(self.variables, *args)

    def __call__(self, image1, image2, iters: int, flow_init=None):
        """Back-compat numpy-out forward: ONE explicit batched pull for
        both outputs (the eval-side analogue of the Logger's
        one-get-per-window)."""
        return jax.device_get(
            self.forward_device(image1, image2, iters, flow_init)
        )

    def metrics(
        self, batch: dict, *, iters: int, acc, kind: str, pad=None,
        flow_init=None, policy=None,
    ):
        """Forward + on-device metric fold in ONE jitted program.

        ``batch`` holds ``image1``/``image2`` (padded) plus ``flow`` and
        optionally ``valid``/``band`` at native shape; ``pad`` is the
        static ``InputPadder.pad_spec``. Returns the updated accumulator
        (device-resident). No flow field ever reaches the host.

        The frames come at the width the pass staged them
        (evaluation._stage_batch: uint8 from every dataset) and the
        program's first act is their widening to float32, exact, so the
        model reads its float32 [0, 255] contract either way. The frames'
        dtypes close the executable's key (the first call AOT-compiles
        for its avals: a uint8 batch must not meet the float32
        executable of its shape); for float32 frames the cast traces to
        nothing and the program is the one it always was.

        ``flow_init`` (warm-start validation): a device-resident
        (B, H/8, W/8, 2) initial low-res flow; when given the program
        additionally returns the final low-res flow so the caller can
        carry it to the next frame — the return becomes
        ``(acc, flow_lr)`` instead of ``acc``, and the warm-start chain
        stays entirely on device (evaluation._run_warmstart_metric_pass
        splats it with ops/warmstart.forward_interpolate_jax).

        The accumulator is deliberately NOT donated: donating an operand
        that is still pending (each batch's ``acc`` is the previous
        batch's not-yet-computed output) makes ``jit`` dispatch wait for
        it — measured ~220 ms/call of lost overlap on the CPU backend —
        and the buffer is a handful of floats, so donation saves nothing.
        """
        extras = {
            k: batch[k] for k in ("flow", "valid", "band") if k in batch
        }
        warm = flow_init is not None
        model, pol = self.model_for(policy)
        key = (
            "metrics",
            tuple(batch["image1"].shape),
            tuple(batch["flow"].shape),
            tuple(sorted(extras)),
            iters,
            kind,
            pad,
            warm,
            # Behind the fields the cost ledger's meta and the benchmark's
            # roofline reader find by place, before the policy's name,
            # which stays the key's last word.
            tuple(str(batch[k].dtype) for k in ("image1", "image2")),
            pol.fingerprint(),
        )

        def build():
            mesh = self.mesh

            def widen(i1, i2):
                return i1.astype(FRAME_DTYPE), i2.astype(FRAME_DTYPE)

            if warm:

                def fn(v, i1, i2, extra, acc_in, finit):
                    def head(flow_up):
                        return metrics_mod.accumulate(
                            kind,
                            acc_in,
                            flow_up,
                            extra["flow"],
                            valid=extra.get("valid"),
                            band=extra.get("band"),
                            pad=pad,
                        )

                    flow_lr, acc_out = model.apply(
                        v, *widen(i1, i2), iters=iters, flow_init=finit,
                        test_mode=True, mesh=mesh, metric_head=head,
                    )
                    return acc_out, flow_lr

                return self._jit(fn, 2, 3, n_out=2)

            def fn(v, i1, i2, extra, acc_in):
                def head(flow_up):
                    return metrics_mod.accumulate(
                        kind,
                        acc_in,
                        flow_up,
                        extra["flow"],
                        valid=extra.get("valid"),
                        band=extra.get("band"),
                        pad=pad,
                    )

                _, acc_out = model.apply(
                    v, *widen(i1, i2), iters=iters, test_mode=True,
                    mesh=mesh, metric_head=head,
                )
                return acc_out

            return self._jit(fn, 2, 2, n_out=1)

        args = (self.variables, batch["image1"], batch["image2"], extras, acc)
        if warm:
            args += (flow_init,)
        return self._get(key, build)(*args)
