"""Serving request/response protocol and per-run accounting.

Every request submitted to the server terminates in exactly ONE of five
explicit statuses — there is no silent-drop path, and a client can
always distinguish "retry later" from "your input is bad" from "the
server failed":

- ``ok``       — flow computed; ``flow`` holds the (H, W, 2) field and
  ``iters`` the budget level it was computed at (the anytime contract:
  fewer iterations under load is a coarser but valid answer).
- ``shed``     — admission refused (queue at capacity, or the server is
  draining). ``retry_after_s`` carries the backpressure hint.
- ``timeout``  — the request's deadline expired while it waited in the
  queue; no compute was spent on it.
- ``rejected`` — the request itself is poison (bad shape/dtype/ndim at
  admission, or non-finite pixels found at dispatch) and was quarantined
  away from its batch-mates; ``detail`` says why.
- ``error``    — the server failed internally while processing the
  batch; the fault is the server's, not the request's.

``ServeStats`` follows ``resilience/retry.RetryStats``'s discipline:
thread-safe (submit callers, the dispatcher, and the drain worker all
mutate it concurrently), mutated only through ``note_*`` methods, and
rendered into one summary line so a run that survived on shedding and
quarantine says so. Each ``note_*`` additionally mirrors into the
telemetry registry under the canonical ``snake_case`` counter name
(``observability.telemetry.LEGACY_KEY_ALIASES["serve"]`` — the pinned
alias table); the legacy summary/report keys here never change.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence

from raft_ncup_tpu.observability.telemetry import LEGACY_KEY_ALIASES

_SERVE_CANON = LEGACY_KEY_ALIASES["serve"]

def nearest_rank_ms(latencies_s: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile of a latency sample, in milliseconds.

    The textbook estimator — value at index ``ceil(p*n) - 1`` of the
    sorted sample (p50 of 16 values is the 8th smallest, not the 9th a
    floor-index would give) — shared by serve.py and the benchmark so the
    reported percentiles mean the same thing everywhere. ``None`` on an empty sample.
    """
    if not latencies_s:
        return None
    xs = sorted(latencies_s)
    idx = max(0, math.ceil(p * len(xs)) - 1)
    return round(xs[min(idx, len(xs) - 1)] * 1000.0, 1)


STATUS_OK = "ok"
STATUS_SHED = "shed"
STATUS_TIMEOUT = "timeout"
STATUS_REJECTED = "rejected"
STATUS_ERROR = "error"

TERMINAL_STATUSES = (
    STATUS_OK,
    STATUS_SHED,
    STATUS_TIMEOUT,
    STATUS_REJECTED,
    STATUS_ERROR,
)


@dataclass
class FlowRequest:
    """One frame pair awaiting flow. ``deadline`` is an absolute time on
    the server's clock (``None`` = no deadline); ``shape_key`` is filled
    at admission — the padded (H, W) bucket the request batches under."""

    request_id: int
    image1: Any  # host array-likes; validated at admission/dispatch
    image2: Any
    deadline: Optional[float] = None
    submit_time: float = 0.0
    shape_key: Optional[tuple] = None
    pad_spec: Optional[tuple] = None
    native_hw: Optional[tuple] = None
    # Cross-process trace id adopted from an inbound TraceContext (a
    # fleet router's wire header) — carried onto this request's spans so
    # one trace_id reassembles the journey across the process boundary
    # (observability/spans.py; docs/OBSERVABILITY.md).
    trace_id: Optional[str] = None


@dataclass
class FlowResponse:
    """Terminal answer for one request (see module docstring)."""

    request_id: int
    status: str
    flow: Optional[Any] = None  # (H, W, 2) numpy, native shape; ok only
    iters: Optional[int] = None  # budget level the flow was computed at
    latency_s: Optional[float] = None  # submit -> completion
    retry_after_s: Optional[float] = None  # shed only: backpressure hint
    detail: str = ""

    @property
    def ok(self) -> bool:
        return self.status == STATUS_OK


class ServeHandle:
    """Thread-safe completion handle handed back by ``submit``.

    ``result(timeout)`` blocks until the terminal response exists; the
    server completes each handle exactly once (a second completion is a
    server bug and raises)."""

    __slots__ = ("_event", "_response")

    def __init__(self) -> None:
        self._event = threading.Event()
        self._response: Optional[FlowResponse] = None

    def complete(self, response: FlowResponse) -> None:
        if self._event.is_set():
            raise RuntimeError(
                f"handle for request {response.request_id} completed twice"
            )
        self._response = response
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> FlowResponse:
        if not self._event.wait(timeout):
            raise TimeoutError("serve handle not completed in time")
        assert self._response is not None
        return self._response


@dataclass(eq=False)  # a counter object: identity, not value, equality
class ServeStats:
    """Per-run serving accounting, rendered into the drain report.

    Mutate through the ``note_*`` methods only (the admission path, the
    dispatcher thread, and the drain worker all write concurrently)."""

    submitted: int = 0
    accepted: int = 0
    completed: int = 0  # ok responses delivered
    shed: int = 0
    timeouts: int = 0
    rejected: int = 0
    errors: int = 0
    batches: int = 0
    padded_rows: int = 0  # dummy rows added to reach a fixed batch program
    quarantined: List[int] = field(default_factory=list)  # poison request ids
    # Telemetry hub to mirror into (observability/; None = no mirror).
    # The local fields above stay the report()/summary() source of truth.
    telemetry: Optional[Any] = field(default=None, repr=False, compare=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def _mirror(self, field_name: str, delta: int = 1) -> None:
        # Outside the stats lock: the registry has its own, and holding
        # both would order them differently on different call paths.
        if self.telemetry is not None:
            self.telemetry.inc(_SERVE_CANON[field_name], delta)

    def note_submitted(self) -> None:
        with self._lock:
            self.submitted += 1
        self._mirror("submitted")

    def note_accepted(self) -> None:
        with self._lock:
            self.accepted += 1
        self._mirror("accepted")

    def note_completed(self) -> None:
        with self._lock:
            self.completed += 1
        self._mirror("completed")

    def note_shed(self) -> None:
        with self._lock:
            self.shed += 1
        self._mirror("shed")

    def note_timeout(self) -> None:
        with self._lock:
            self.timeouts += 1
        self._mirror("timeouts")

    def note_error(self) -> None:
        with self._lock:
            self.errors += 1
        self._mirror("errors")

    def note_batch(self, padded_rows: int) -> None:
        with self._lock:
            self.batches += 1
            self.padded_rows += padded_rows
        self._mirror("batches")
        if padded_rows:
            self._mirror("padded_rows", padded_rows)

    def note_rejected(self, request_id: int, *,
                      quarantine: bool = False) -> None:
        """``quarantine=True`` marks a dispatch-time poison quarantine
        (the request made it into a batch and was isolated there);
        admission-time validation rejects count as ``rejected`` only —
        the drain report's ``quarantined=[...]`` list means exactly
        "poison isolated from live batch-mates"."""
        with self._lock:
            self.rejected += 1
            if quarantine and request_id not in self.quarantined:
                self.quarantined.append(request_id)
        self._mirror("rejected")
        if quarantine and self.telemetry is not None:
            self.telemetry.event(
                "serve_request_quarantined", request_id=request_id
            )

    def summary(self) -> str:
        q = ",".join(str(i) for i in self.quarantined) or "-"
        return (
            f"submitted={self.submitted} accepted={self.accepted} "
            f"completed={self.completed} shed={self.shed} "
            f"timeouts={self.timeouts} rejected={self.rejected} "
            f"errors={self.errors} batches={self.batches} "
            f"padded_rows={self.padded_rows} quarantined=[{q}]"
        )
