"""Multi-host (pod) support: process initialization + global batch
assembly.

The reference's entire distributed story is single-process
``nn.DataParallel`` (reference: train.py:169-175; SURVEY.md §2 C21). Here
the same jitted SPMD step runs unchanged on a pod: every host runs the
same program, ``jax.distributed.initialize`` wires the processes into one
runtime, the mesh spans all chips, gradient psums ride ICI within a slice
and DCN between them (XLA routes collectives by mesh topology), and each
host feeds its disjoint input shard (FlowLoader already shards by
``jax.process_index()``).
"""

from __future__ import annotations

import sys
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize the multi-process JAX runtime (no-op when single-process
    or when the TPU pod environment provides the coordination config).

    On Cloud TPU pods, ``jax.distributed.initialize()`` reads everything
    from the environment; explicit args support other clusters.
    """
    if num_processes == 1:
        return
    explicit = coordinator_address is not None or process_id is not None
    if explicit and (num_processes or 0) > 1:
        # Cross-process computations on the CPU backend need an actual
        # collectives transport; without one XLA refuses to compile any
        # multiprocess program ("Multiprocess computations aren't
        # implemented on the CPU backend"). Gloo ships with jaxlib and
        # only affects the CPU backend, so enable it when we are about
        # to join a multi-process runtime — this is what lets the
        # distributed tests (tests/test_multihost.py) run real
        # multi-host SPMD on virtual CPU devices. Guarded to the
        # explicit-args path: touching this config on the no-op
        # single-process path would re-initialize an already-live
        # backend.
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception as e:  # option absent / backend already up
            print(f"cpu collectives not configured: {e}")
    try:
        jax.distributed.initialize(
            coordinator_address, num_processes, process_id
        )
    except (RuntimeError, ValueError, OSError) as e:
        # With explicit coordination args, a failed init must not fall
        # back to independent single-process runs silently (every host
        # would train its own full copy into the same run dir).
        # OSError: on a TPU host jax's cluster auto-detection asks the
        # metadata server for the coordinator; a single-chip machine
        # without one answers with a connection error (first chip run,
        # PR 21: `python train.py` died here before its first step).
        if explicit:
            raise
        # No coordination config: single-process run. Log loudly rather
        # than swallowing, so a misconfigured pod is visible in the logs.
        if "already initialized" not in str(e).lower():
            print(f"jax.distributed.initialize skipped: {e}")


def global_batch(batch: dict, mesh: Mesh, shardings: dict) -> dict:
    """Assemble per-host local batches into global sharded arrays.

    Each host passes its local slice (the FlowLoader shard); the result is
    a dict of global ``jax.Array`` whose shards live where the mesh puts
    them — the multi-host replacement for passing host-local numpy straight
    into jit (which only works single-process).
    """
    out = {}
    for key, value in batch.items():
        sharding = shardings.get(key)
        if sharding is None:
            out[key] = value
            continue
        out[key] = jax.make_array_from_process_local_data(
            sharding, np.asarray(value)
        )
    return out


def device_put_batch(
    batch: dict, mesh: Optional[Mesh], shardings: Optional[dict]
) -> dict:
    """Move one host-local batch dict onto device — the single transfer
    policy shared by the train loop's async prefetcher and the bench's
    pipelined-loop row.

    Multi-host with a mesh: each host contributes its local shard and the
    result is a dict of global ``jax.Array`` (:func:`global_batch`).
    Single-process with shardings: ``jax.device_put`` straight into the
    batch sharding's layout, so the jitted step's dispatch does no
    re-layout. No shardings: default device placement.
    """
    if mesh is not None and shardings is not None and is_multihost():
        return global_batch(batch, mesh, shardings)
    shardings = shardings or {}
    return {
        key: jax.device_put(np.asarray(value), shardings.get(key))
        for key, value in batch.items()
    }


def is_multihost() -> bool:
    return jax.process_count() > 1


def is_main_process() -> bool:
    """True on exactly one process per job — the only one that should
    write human-facing output (log files, TensorBoard, submissions).
    Orbax checkpoint saves stay all-process (orbax coordinates its own
    per-host shard writes)."""
    return jax.process_index() == 0


def allreduce_sum_across_hosts(x) -> np.ndarray:
    """Sum a host-local numpy accumulator over all processes.

    The multi-host reduction for host-sharded validation: each process
    validates its slice of the frames and the fixed-size metric
    accumulator (sums and counts, NOT means) is summed across hosts so
    every process returns identical global metrics. Single-process: a
    cheap pass-through. Requires the same accumulator shape on every
    process (``process_allgather`` stages one collective)."""
    x = np.asarray(x)
    if not is_multihost():
        return x
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x)).sum(axis=0)


def barrier(name: str, timeout_s: float = 480.0) -> bool:
    """Block until every process reaches this barrier (coordination
    service — no device collectives involved, so it tolerates arbitrary
    cross-process skew, unlike Gloo/ICI ops whose context init has a
    hard ~30s deadline). Use it to align processes before the first
    collective execution when their compile times can drift apart.

    Returns False (after logging) instead of raising when this jax
    build's distributed client doesn't expose the barrier API — the
    jax._src access is isolated HERE so a jax upgrade breaks one
    maintained helper, not every caller.
    """
    if not is_multihost():
        return True
    try:
        from jax._src import distributed

        distributed.global_state.client.wait_at_barrier(
            name, timeout_in_ms=int(timeout_s * 1000)
        )
        return True
    except (ImportError, AttributeError, TypeError) as e:
        # TypeError included: the unstable jax._src signature changing
        # (e.g. the timeout keyword renamed) must degrade like the API
        # being absent, per this helper's contract.
        # stderr: child stdout is a parsed protocol stream in the tooling
        # around this helper (tests/_distributed_child.py's LOSS= lines)
        # — diagnostics must not mix in.
        print(
            f"multihost barrier unavailable ({e}); proceeding unaligned",
            file=sys.stderr,
        )
        return False


def replicated_hosts_sharding(mesh: Mesh) -> NamedSharding:
    from jax.sharding import PartitionSpec as P

    return NamedSharding(mesh, P())
