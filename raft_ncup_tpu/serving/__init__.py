"""Online flow serving: admission control, backpressure, anytime
iteration budgets, and chaos-tested graceful drain.

The train and eval hot loops batch *known* work; a service faces an
open-loop request stream it does not control. This package is the
robustness layer between that stream and the bounded executable set the
inference stack already provides (``ops/padding.InputPadder(bucket=N)``
+ ``inference/pipeline.ShapeCachedForward`` LRU + ``DispatchThrottle``):

- :mod:`request` — the request/response protocol: explicit terminal
  statuses (``ok`` / ``shed`` / ``timeout`` / ``rejected`` / ``error``),
  a thread-safe completion handle, and ``ServeStats`` accounting in the
  ``resilience/retry.RetryStats`` discipline (a server that survived on
  shedding and quarantine says so).
- :mod:`admission` — a bounded FIFO admission queue with load-shedding:
  a full queue REJECTS with a ``retry_after_s`` hint instead of queueing
  unboundedly (open-loop arrivals + unbounded queue = unbounded p99).
- :mod:`budget` — the load-adaptive iteration budget controller. RAFT's
  iterative refinement is a native anytime knob (PAPERS.md:
  arXiv:2003.12039): fewer GRU iterations is a coarser but valid flow
  field, so under burst the server degrades EPE instead of latency. The
  level set is small and fixed with hysteresis between moves, so the
  compiled executable set stays bounded and recompile-free.
- :mod:`server` — :class:`~raft_ncup_tpu.serving.server.FlowServer`:
  dynamic micro-batching over the bounded shape/batch/iter program set,
  per-request deadlines, poison-request quarantine (a bad shape/dtype/
  NaN input is rejected alone; its batch-mates are unaffected), and
  graceful drain (stop admitting, flush everything admitted, report).
- :mod:`traffic` — the deterministic synthetic traffic generator and
  replay driver; ``resilience/chaos.py``'s ``burst@N`` / ``poison@N`` /
  ``sigterm@N`` events drive the end-to-end chaos tests
  (tests/test_serving.py) and the ``serve.py`` demo loop.

Semantics, the executable-set arithmetic, and the chaos matrix:
docs/SERVING.md. Benchmark: the cell ``serve_sintel_raft``
(benchmark/README.md).
"""

from raft_ncup_tpu.serving.admission import AdmissionQueue  # noqa: F401
from raft_ncup_tpu.serving.budget import (  # noqa: F401
    IterationBudgetController,
)
from raft_ncup_tpu.serving.request import (  # noqa: F401
    STATUS_ERROR,
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHED,
    STATUS_TIMEOUT,
    TERMINAL_STATUSES,
    FlowRequest,
    FlowResponse,
    ServeHandle,
    ServeStats,
    nearest_rank_ms,
)

# FlowServer/traffic import the inference stack (and through it jax);
# they resolve lazily (PEP 562) so the host-only consumers of the
# request protocol — the fleet router above all (JGL010: fleet/ must
# never import jax, even transitively through this package) — can
# import `raft_ncup_tpu.serving.request` without initializing a backend.
_LAZY = {
    "FlowServer": ("raft_ncup_tpu.serving.server", "FlowServer"),
    "SyntheticTraffic": ("raft_ncup_tpu.serving.traffic", "SyntheticTraffic"),
    "replay": ("raft_ncup_tpu.serving.traffic", "replay"),
}


def __getattr__(name: str):
    try:
        module, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    import importlib

    value = getattr(importlib.import_module(module), attr)
    globals()[name] = value  # cache: one lazy resolve per process
    return value

__all__ = [
    "AdmissionQueue",
    "FlowRequest",
    "FlowResponse",
    "FlowServer",
    "IterationBudgetController",
    "STATUS_ERROR",
    "STATUS_OK",
    "STATUS_REJECTED",
    "STATUS_SHED",
    "STATUS_TIMEOUT",
    "TERMINAL_STATUSES",
    "ServeHandle",
    "ServeStats",
    "SyntheticTraffic",
    "nearest_rank_ms",
    "replay",
]
