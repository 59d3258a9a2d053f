"""Runtime guard rails: assert the sync-free, recompile-free hot path live.

graftlint (the sibling ``lint`` module) proves statically that traced code
contains no host syncs; this module asserts the same invariants on the
*running* loop, where the failure modes static analysis cannot see live:
dispatch-time implicit transfers, and silent recompilation from shape or
dtype drift. Three primitives:

- :func:`forbid_host_transfers` — context manager that intercepts
  implicit device→host pulls (``float()``/``int()``/``bool()``/
  ``.item()``/``.tolist()``/``np.asarray``/``np.array`` on a
  ``jax.Array``) and raises :class:`GuardViolation` (or counts, with
  ``raise_on_violation=False``). The *explicit* ``jax.device_get`` stays
  sanctioned — it is the contract for window-boundary pulls (the
  Logger's one-get-per-``sum_freq``; the eval loop's one-get-per-window).
  Layered on top, ``jax.transfer_guard_device_to_host("disallow")``
  catches native-path transfers on real accelerators; the Python-level
  interception exists because on the CPU backend device→host is zero-copy
  and the native guard never fires — without it the tier-1 tests would
  vacuously pass.
- :class:`RecompileWatchdog` / :func:`max_recompiles` — counts XLA
  backend compiles via ``jax.monitoring``'s
  ``/jax/core/compile/backend_compile_duration`` event; ``max_recompiles``
  raises at scope exit when the count exceeds the budget (a steady-state
  train loop compiles its step exactly once).
- :class:`StepGuard` — the train-loop integration (``--strict_guards``):
  registered once for the loop, armed per step via :meth:`StepGuard.scope`
  so validation/checkpoint boundaries (which legitimately pull to host
  and compile new shapes) stay outside the guarded region.

Interception patches are process-global while a scope is active (a
violating pull from *any* thread is a violation — the DevicePrefetcher
worker only does host→device work and is unaffected); the sanctioning
flag is thread-local so one thread's ``jax.device_get`` cannot blanket
another thread's stray pull.

tests/conftest.py re-exports :func:`forbid_host_transfers` and
:func:`max_recompiles` as pytest fixtures; tests/test_guards.py pins the
train loop's invariants with them. docs/ANALYSIS.md documents the layer.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Iterator, List, Optional

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Implicit-pull methods intercepted on the concrete array type. __array__
# covers jax.device_get's own path and (on non-CPU backends) np.asarray;
# on CPU, np.asarray takes the buffer protocol around __array__, which is
# why numpy's module-level asarray/array are wrapped as well.
_PULL_METHODS = (
    "__array__",
    "__float__",
    "__int__",
    "__bool__",
    "__complex__",
    "item",
    "tolist",
)
_NUMPY_FUNCS = ("asarray", "array")


class GuardViolation(RuntimeError):
    """A guarded invariant (no implicit host pulls / compile budget) broke."""


@dataclass(eq=False)  # a counter object: identity, not value, equality
class GuardStats:
    """Counters a guard scope fills in; --strict_guards reports these."""

    host_transfers: int = 0  # forbidden implicit pulls observed
    sanctioned_gets: int = 0  # explicit jax.device_get calls
    recompiles: int = 0  # steady-state compiles (see StepGuard)
    warmup_compiles: int = 0  # first-scope compiles (step + aux programs)
    violations: List[str] = field(default_factory=list)


def _array_impl_type():
    from jax._src.array import ArrayImpl

    return ArrayImpl


# ----------------------------------------------------------- pull guard

_tl = threading.local()  # .sanctioned: inside an explicit device_get
_lock = threading.RLock()
_active: list = []  # stack of _ScopeEntry (patches installed while non-empty)
_saved: dict = {}


class _ScopeEntry:
    """One active guard scope. ``armed=False`` keeps the patches installed
    but inert — StepGuard's between-step state, so the hot loop never
    pays per-step install/uninstall (the watchdog's arm()/disarm()
    pattern applied to the pull patches)."""

    __slots__ = ("stats", "raise_on_violation", "armed")

    def __init__(self, stats, raise_on_violation: bool, armed: bool = True):
        self.stats = stats
        self.raise_on_violation = raise_on_violation
        self.armed = armed


def _push_scope(
    stats: "GuardStats", raise_on_violation: bool, armed: bool = True
) -> _ScopeEntry:
    with _lock:
        if not _active:
            _install()
        entry = _ScopeEntry(stats, raise_on_violation, armed)
        _active.append(entry)
        return entry


def _pop_scope(entry: _ScopeEntry) -> None:
    with _lock:
        _active.remove(entry)  # identity-based: plain object equality
        if not _active:
            _uninstall()


def _record_violation(desc: str) -> None:
    with _lock:
        entry = next((e for e in reversed(_active) if e.armed), None)
        if entry is None:
            return
        entry.stats.host_transfers += 1
        entry.stats.violations.append(desc)
        raise_on_violation = entry.raise_on_violation
    # Mirror into the unified telemetry stream (observability/): a guard
    # violation is exactly the kind of lifecycle fact a later stall
    # diagnosis wants on the correlated timeline. GuardStats stays the
    # scope-local source of truth.
    from raft_ncup_tpu.observability import get_telemetry

    tel = get_telemetry()
    tel.event("guard_host_transfer_violation", desc=desc)
    # Fault trigger (observability/flight.py): a guard violation means
    # a sync leaked onto the hot path — bank the timeline that led to
    # it. Rate-limited in the recorder, no-op without one.
    tel.flight_dump("guard_violation", desc=desc)
    if raise_on_violation:
        raise GuardViolation(
            f"implicit device->host transfer under forbid_host_transfers: "
            f"{desc}. Keep values on device between window boundaries and "
            "batch explicit pulls through one jax.device_get."
        )


def _install() -> None:
    import numpy as np

    arr_t = _array_impl_type()
    for name in _PULL_METHODS:
        orig = getattr(arr_t, name)
        _saved[("arr", name)] = orig

        def make(nm, o):
            def patched(self, *a, **kw):
                if not getattr(_tl, "sanctioned", False):
                    _record_violation(
                        f"jax.Array.{nm} on shape {getattr(self, 'shape', '?')}"
                    )
                return o(self, *a, **kw)

            return patched

        setattr(arr_t, name, make(name, orig))

    for name in _NUMPY_FUNCS:
        orig = getattr(np, name)
        _saved[("np", name)] = orig

        def make_np(nm, o):
            def patched(obj, *a, **kw):
                if isinstance(obj, arr_t) and not getattr(
                    _tl, "sanctioned", False
                ):
                    _record_violation(
                        f"np.{nm} on jax.Array of shape "
                        f"{getattr(obj, 'shape', '?')}"
                    )
                return o(obj, *a, **kw)

            return patched

        setattr(np, name, make_np(name, orig))

    orig_get = jax.device_get
    _saved[("jax", "device_get")] = orig_get

    def sanctioned_get(tree):
        with _lock:
            entry = next((e for e in reversed(_active) if e.armed), None)
            if entry is not None:
                entry.stats.sanctioned_gets += 1
        if entry is not None:
            # Canonical counter for GuardStats.sanctioned_gets (host
            # int bump — the pull itself is unaffected).
            from raft_ncup_tpu.observability import get_telemetry

            get_telemetry().inc("guard_sanctioned_gets_total")
        prev = getattr(_tl, "sanctioned", False)
        _tl.sanctioned = True
        try:
            return orig_get(tree)
        finally:
            _tl.sanctioned = prev

    jax.device_get = sanctioned_get


def _uninstall() -> None:
    import numpy as np

    arr_t = _array_impl_type()
    for (kind, name), orig in _saved.items():
        target = {"arr": arr_t, "np": np, "jax": jax}[kind]
        setattr(target, name, orig)
    _saved.clear()


@contextlib.contextmanager
def forbid_host_transfers(
    stats: Optional[GuardStats] = None,
    raise_on_violation: bool = True,
    native_guard: bool = True,
) -> Iterator[GuardStats]:
    """Forbid implicit device→host pulls inside the scope.

    Yields the :class:`GuardStats` being filled. With
    ``raise_on_violation=False`` violations only count. ``native_guard`` additionally arms jax's own
    ``transfer_guard_device_to_host("disallow")`` — real coverage on
    accelerators, inert on zero-copy CPU.
    """
    stats = stats if stats is not None else GuardStats()
    entry = _push_scope(stats, raise_on_violation)
    native = (
        jax.transfer_guard_device_to_host("disallow")
        if native_guard
        else contextlib.nullcontext()
    )
    try:
        with native:
            yield stats
    finally:
        _pop_scope(entry)


# ----------------------------------------------------- recompile watchdog


class RecompileWatchdog:
    """Counts XLA backend compiles while armed (jax.monitoring listener).

    Use as a context manager; ``.count`` is the number of compiles
    observed inside the scope. ``arm()``/``disarm()`` gate counting
    within a longer registration (StepGuard counts step-scope compiles
    only, not validation's)."""

    def __init__(self) -> None:
        self.count = 0
        self._armed = True
        self._registered = False

    def _listener(self, event: str, duration: float, **kw) -> None:
        if self._armed and event.startswith(_COMPILE_EVENT):
            self.count += 1
            from raft_ncup_tpu.observability import get_telemetry

            get_telemetry().inc("guard_recompiles_total")

    def arm(self) -> None:
        self._armed = True

    def disarm(self) -> None:
        self._armed = False

    def __enter__(self) -> "RecompileWatchdog":
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        self._registered = True
        return self

    def __exit__(self, *exc) -> None:
        if not self._registered:
            return
        self._registered = False
        try:
            from jax._src import monitoring as _mon

            _mon._unregister_event_duration_listener_by_callback(
                self._listener
            )
        except Exception:
            # Private unregister API moved: leave the listener registered
            # but permanently disarmed — correct, just not tidy.
            self._armed = False


@contextlib.contextmanager
def max_recompiles(n: int = 1) -> Iterator[RecompileWatchdog]:
    """Assert at most ``n`` XLA compiles happen inside the scope; raises
    :class:`GuardViolation` at exit otherwise. A fixed-shape train loop
    compiles its step once — every extra compile is shape/dtype drift
    silently re-paying (multi-minute, at scale) compile latency."""
    with RecompileWatchdog() as wd:
        yield wd
    if wd.count > n:
        raise GuardViolation(
            f"{wd.count} XLA compiles inside a max_recompiles({n}) scope — "
            "an input aval (shape/dtype/sharding) is drifting between steps"
        )


# --------------------------------------------------------- loop integration


class StepGuard:
    """``--strict_guards`` integration for a training loop.

    Register once around the loop (context manager), then wrap each
    steady-state iteration in :meth:`scope`::

        with StepGuard() as guard:
            while step_i < total:
                with guard.scope():
                    batch = next(prefetcher)   # device-resident already
                    state, metrics = step_fn(state, batch, rng)
                    logger.push(...)           # explicit get at boundary ok
                if step_i % val_freq == 0:
                    validate(...)              # outside: may pull/compile
            guard.check()

    Inside ``scope()``: implicit host pulls raise immediately; compiles
    are counted. Outside: nothing is patched or counted, so validation
    and checkpointing behave normally.

    Compile accounting is per scope: the first ``warmup_scopes`` scopes
    legitimately compile the train step plus its small satellite programs
    and are recorded as ``stats.warmup_compiles``; compiles in any LATER
    scope land in ``stats.recompiles`` and mean an input aval is
    drifting. The default warm-up is TWO scopes, not one: the step, rng
    fold-in etc. compile in scope 0, but the Logger's on-device metric
    accumulate (``prev + v``) first runs — and compiles — at push #2,
    i.e. in scope 1. :meth:`check` enforces
    ``stats.recompiles <= max_steady_recompiles`` (default 0 — a
    steady-state loop never compiles).
    """

    def __init__(
        self,
        max_steady_recompiles: int = 0,
        raise_on_violation: bool = True,
        warmup_scopes: int = 2,
    ) -> None:
        self.max_steady_recompiles = max_steady_recompiles
        self.raise_on_violation = raise_on_violation
        self.warmup_scopes = warmup_scopes
        self.stats = GuardStats()
        self._watchdog = RecompileWatchdog()
        self._entry: Optional[_ScopeEntry] = None
        self._scopes = 0

    def __enter__(self) -> "StepGuard":
        self._watchdog.__enter__()
        self._watchdog.disarm()
        # Patches install ONCE here and stay (disarmed) between scopes:
        # per-step install/uninstall would put ~20 setattrs on the exact
        # loop this subsystem exists to keep host-light.
        self._entry = _push_scope(
            self.stats, self.raise_on_violation, armed=False
        )
        return self

    def __exit__(self, *exc) -> None:
        if self._entry is not None:
            _pop_scope(self._entry)
            self._entry = None
        self._watchdog.__exit__(*exc)

    @contextlib.contextmanager
    def scope(self) -> Iterator[None]:
        """One guarded steady-state iteration."""
        before = self._watchdog.count
        self._watchdog.arm()
        self._entry.armed = True
        try:
            with jax.transfer_guard_device_to_host("disallow"):
                yield
        finally:
            self._entry.armed = False
            self._watchdog.disarm()
            delta = self._watchdog.count - before
            if self._scopes < self.warmup_scopes:
                self.stats.warmup_compiles += delta
            else:
                self.stats.recompiles += delta
            self._scopes += 1

    def check(self) -> None:
        """Enforce the steady-state compile budget over all scopes so far."""
        if self.stats.recompiles > self.max_steady_recompiles:
            raise GuardViolation(
                f"train step recompiled {self.stats.recompiles}x after its "
                f"warm-up scope (budget {self.max_steady_recompiles}) — an "
                "input aval (shape, dtype or sharding) is drifting between "
                "steps"
            )
