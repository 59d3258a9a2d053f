"""Device-side batch prefetching: overlap host→device transfer with compute.

RAFT's recurrent step chains 12 GRU iterations, so every training step is
latency-bound — there is no slack inside the step to hide input stalls.
The FlowLoader already overlaps *decode/augment* with training (its own
thread pool + host-batch queue), but the host→device transfer and the
global-array assembly still sat on the critical path in the train loop:
``jnp.asarray``/``global_batch`` ran serially between dispatching step N
and step N+1.

:class:`DevicePrefetcher` closes that gap. A single worker thread pulls
host batches from the wrapped iterator, moves each to device (the batch
sharding's layout, so jit dispatch does no re-layout) and parks up to
``depth`` device-resident batches in a bounded queue. In steady state the
consumer's ``next()`` returns an array that is already on device — the
accelerator never waits on the host for input.

Contracts:

- **Order-preserving**: one worker thread, one FIFO queue — batches come
  out in exactly the wrapped iterator's order, contents untouched (only
  ``drop_keys`` removed and leaves transferred).
- **Exception propagation**: any error in the worker (including errors
  the wrapped iterator raises, e.g. FlowLoader surfacing a decode
  failure) is re-raised from the consumer's ``next()``.
- **Clean shutdown**: ``close()`` (or the context manager) stops the
  worker even while it is blocked on a full queue, joins it, and closes
  the wrapped iterator. Safe to call more than once.

Spans (docs/OBSERVABILITY.md), shared by the train and the eval loop, on
the process hub unless one is passed, each with the batch's index
``batch`` and the caller's ``span_attrs`` (the eval pass's ``pass_id``):
``input_stage`` (worker thread: the wait for the wrapped iterator's next
host batch — decode wait, pad, stack), ``input_h2d`` (worker thread: the
transfer, waited for until the batch is resident) and ``input_wait``
(consumer thread: how long ``next()`` waited for a staged batch — the
only one of the three on the critical path).
The wait that ends in exhaustion records nothing, so each count is the
count of batches. Once per prefetcher, ``input_start`` (with the caller's
``span_attrs``): from construction to the first batch resident on the
device and queued (worker spawn, the first ``input_stage`` and
``input_h2d``); it starts on the constructing thread and ends on the
worker, so its profiler annotation begins where the worker does. The
process's first is banked as the start-up record's ``input_start_s``
(``observability/startup.py``); an empty iterator records none.

Transfer policy lives in :func:`raft_ncup_tpu.parallel.multihost.
device_put_batch`: ``jax.device_put`` against the batch sharding on the
single-process path, ``jax.make_array_from_process_local_data`` on a pod.
"""

from __future__ import annotations

import queue
import sys
import threading
from typing import Any, Iterable, Iterator, Mapping, Optional

import jax

from raft_ncup_tpu.observability import (
    StartupPhase,
    get_startup_record,
    get_telemetry,
)
from raft_ncup_tpu.utils.profiling import annotate_spans

# Queue sentinel: the wrapped iterator was exhausted (finite iterators —
# FlowLoader.batches() is infinite, but tests and epoch-bounded consumers
# are not).
_END = object()


class DevicePrefetcher:
    """Wrap an iterator of host batch dicts; yield device-resident batches
    ``depth`` steps ahead of the consumer.

    Parameters
    ----------
    batches:
        Iterator/iterable of ``dict[str, np.ndarray]`` host batches (the
        FlowLoader contract).
    depth:
        Number of device batches staged ahead of compute. ``>= 2`` keeps
        one batch in flight while the next transfers — the minimum for
        full overlap of transfer with the compiled step.
    mesh / shardings:
        Forwarded to :func:`device_put_batch`; ``None`` means default
        device placement (single chip, no mesh).
    drop_keys:
        Batch keys removed before transfer (non-array metadata such as
        ``extra_info``).
    telemetry / span_attrs:
        The hub the ``input_*`` spans go to (``None``: the process hub)
        and host-scalar attributes every one of them carries.
    """

    def __init__(
        self,
        batches: Iterable[Mapping[str, Any]],
        *,
        depth: int = 2,
        mesh=None,
        shardings: Optional[dict] = None,
        drop_keys: tuple[str, ...] = ("extra_info",),
        telemetry=None,
        span_attrs: Optional[Mapping[str, Any]] = None,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._tel = telemetry if telemetry is not None else get_telemetry()
        annotate_spans(self._tel)
        self._span_attrs = dict(span_attrs or {})
        self._taken = 0  # consumer side: batches handed out
        self._it = iter(batches)
        self._mesh = mesh
        self._shardings = shardings
        self._drop_keys = frozenset(drop_keys or ())
        self._stop = threading.Event()
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._start = StartupPhase(self._tel, "input_start", **self._span_attrs)
        self._thread = threading.Thread(
            target=self._worker, name="device-prefetch", daemon=True
        )
        self._thread.start()

    # ---------------------------------------------------------- worker side

    def _transfer(self, batch: Mapping[str, Any]) -> dict:
        from raft_ncup_tpu.parallel.multihost import device_put_batch

        host = {k: v for k, v in batch.items() if k not in self._drop_keys}
        return device_put_batch(host, self._mesh, self._shardings)

    def _put(self, item) -> bool:
        """Bounded put that keeps checking for shutdown — a consumer that
        stopped pulling must not strand the worker on a full queue."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _stage(self, index: int):
        """One batch from the wrapped iterator to the queue; False where
        the iterator is exhausted or the prefetcher closed."""
        attrs = {"batch": index, **self._span_attrs}
        with self._tel.span("input_stage", **attrs) as span:
            try:
                batch = next(self._it)
            except StopIteration:
                span.discard()
                self._put(_END)
                return False
        with self._tel.span("input_h2d", **attrs):
            # device_put only enqueues the copy (2 ms for 115 MB on
            # a v5e, PERF.md PR 24). Waiting for it here, off the
            # critical path, makes the span the copy's own time and
            # hands the consumer a batch that is really resident.
            device_batch = jax.block_until_ready(self._transfer(batch))
        return self._put(device_batch)

    def _worker(self) -> None:
        try:
            with self._start as start:
                if self._stop.is_set() or not self._stage(0):
                    start.discard()
                    return
            get_startup_record().phase("input_start_s", start.seconds)
            index = 1
            while not self._stop.is_set() and self._stage(index):
                index += 1
        except BaseException as e:  # noqa: BLE001 — surfaced to consumer
            self._put(e)
        finally:
            # The worker is the only thread ever executing the wrapped
            # generator, and it is suspended (not executing) here — so
            # this is the one place its close() is always legal. A close
            # failure has no consumer left to surface to, but it must not
            # vanish either (JGL007): log it to stderr.
            close = getattr(self._it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception as e:
                    print(
                        f"device-prefetch: wrapped iterator close failed: "
                        f"{e}",
                        file=sys.stderr,
                    )

    # -------------------------------------------------------- consumer side

    def __iter__(self) -> Iterator[dict]:
        return self

    def __next__(self) -> dict:
        with self._tel.span(
            "input_wait", batch=self._taken, **self._span_attrs
        ) as span:
            try:
                item = self._next_item()
            except BaseException:
                span.discard()  # exhausted, closed or failed: not a batch
                raise
        self._taken += 1
        return item

    def _next_item(self) -> dict:
        while True:
            if self._stop.is_set():
                raise StopIteration
            try:
                item = self._q.get(timeout=0.5)
            except queue.Empty:
                if not self._thread.is_alive():
                    raise RuntimeError(
                        "device-prefetch worker died without delivering a "
                        "batch or an exception"
                    ) from None
                continue
            if item is _END:
                self._stop.set()  # exhausted: later next() calls stay StopIteration
                raise StopIteration
            if isinstance(item, BaseException):
                self.close()
                raise item
            return item

    def close(self) -> None:
        """Stop the worker, join it, close the wrapped iterator. Idempotent."""
        self._stop.set()
        # Drain so a worker blocked on a full queue can observe the stop
        # flag on its next put attempt instead of spinning a full timeout.
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10.0)

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
