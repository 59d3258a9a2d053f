"""Fleet tier: a multi-replica router over N serve.py child processes
(ROADMAP item 2; docs/FLEET.md).

Everything below this package is ONE process — one ``FlowServer``, one
``StreamEngine``, one device mesh. "Millions of users" is a *process
topology*: N replica processes (each owning its own devices / mesh
slice) behind a router that admits, routes, and fails over WITHOUT ever
crossing into device land itself. The package is therefore host-only
stdlib + numpy by construction — lint rule JGL010 holds it to the same
no-jax contract as ``observability/``: a router that can touch a device
array can add a device sync to every request it routes.

- :mod:`topology` — one frozen declarative :class:`FleetConfig` (the
  arXiv:2606.11390 one-object pattern applied to process topology):
  replica count, per-replica serve/stream knobs + mesh slice + socket +
  healthz path, router admission bounds, failover/restart budgets.
  The supervisor, the router, bench, chaos, and the tests all read THIS
  object; nothing else defines the fleet's shape.
- :mod:`wire` — the socket frame protocol: length-prefixed JSON header
  + raw C-order ndarray payloads over a Unix domain socket or a TCP
  connection (:class:`wire.Transport` parses the family from the one
  address string both ends share; TCP links are hardened with connect
  timeouts, keepalive, and boundary-vs-mid-frame read deadlines).
- :mod:`replica` — :class:`ChildProcess` (the one process-lifecycle
  implementation: spawn, liveness/healthz wait, drain, reap — shared
  with the 4-process distributed test rig) and
  :class:`ReplicaSupervisor` (healthz polling with the staleness
  contract, SIGTERM→DRAINING→exit-75 drain orchestration, bounded
  counted restart-with-backoff, circuit breaker).
- :mod:`router` — :class:`FleetRouter`: fleet-level admission that
  sheds BEFORE work crosses a process boundary, consistent-hash stream
  affinity, shape-aware request routing against the replicas'
  healthz-advertised warmed executable sets, DRAINING/DEGRADED-aware
  rotation, and deadline-respecting single-failover retry — same
  five-status terminal protocol as ``serving/request.py``.
- :mod:`host_supervisor` — the multi-host control plane: a per-host
  :class:`HostSupervisor` agent (the unmodified ReplicaSupervisor over
  that host's slots + a wire republish of their healthz) and the
  router-side :class:`FleetManager` (fleet-level staleness: a silent
  host is a dead host — fenced, failed over).
- :mod:`autoscaler` — :class:`FleetAutoscaler`: the SLO-driven elastic
  sizing loop (occupancy/burn/shed signals, hysteresis + cooldown,
  scale-up through the READY pre-warm gate, scale-down through the
  zero-loss drain contract, fail-budget breaker, time-to-READY ETA
  published to the router's shed hints).

Chaos: ``killreplica@N`` / ``stallreplica@N`` / ``drainreplica@N`` +
the fleet-scale ``partitionhost@N`` / ``killsupervisor@N``
(resilience/chaos.py) drive the blast-radius tests in
tests/test_fleet.py. No cell of the benchmark runs the fleet
(docs/FLEET.md "Not brought up on the chip yet").
"""

from raft_ncup_tpu.fleet.autoscaler import FleetAutoscaler  # noqa: F401
from raft_ncup_tpu.fleet.host_supervisor import (  # noqa: F401
    FleetManager,
    HostSupervisor,
)

from raft_ncup_tpu.fleet.replica import (  # noqa: F401
    ChildProcess,
    ReplicaHandle,
    ReplicaSupervisor,
    healthz_fresh,
    read_healthz,
)
from raft_ncup_tpu.fleet.router import FleetRouter, replay_fleet  # noqa: F401
from raft_ncup_tpu.fleet.topology import (  # noqa: F401
    FleetConfig,
    ReplicaSpec,
    padded_shape,
)
from raft_ncup_tpu.fleet.wire import (  # noqa: F401
    Transport,
    recv_msg,
    send_msg,
)

__all__ = [
    "ChildProcess",
    "FleetAutoscaler",
    "FleetConfig",
    "FleetManager",
    "FleetRouter",
    "HostSupervisor",
    "Transport",
    "ReplicaHandle",
    "ReplicaSpec",
    "ReplicaSupervisor",
    "healthz_fresh",
    "padded_shape",
    "read_healthz",
    "recv_msg",
    "replay_fleet",
    "send_msg",
]
