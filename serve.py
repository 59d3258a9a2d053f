#!/usr/bin/env python
"""Flow-serving driver: run the online serving tier — or, with
``--stream``, the streaming video engine — against a deterministic
synthetic open-loop schedule.

The serving analogue of train.py/evaluate.py (no reference counterpart —
the reference has no serving story). Default mode builds one model +
variables set, stands up a :class:`raft_ncup_tpu.serving.FlowServer`
(bounded admission queue, anytime iteration budget, poison quarantine),
warms the full executable set, replays ``--num_requests`` synthetic
requests at ``--interval_ms``, then drains and prints ONE JSON report
line (stats + latency percentiles + budget trajectory).

``--stream`` mode stands up a
:class:`raft_ncup_tpu.streaming.StreamEngine` instead (fixed-capacity
slot table, device-resident warm start, per-stream fault isolation;
docs/STREAMING.md) and replays ``--n_streams`` concurrent streams of
``--frames_per_stream`` frames each.

Graceful drain (both modes): SIGTERM/SIGINT (via
``resilience/preemption.py``) stops submissions immediately, everything
already admitted is flushed through compute, and the process exits
``EXIT_PREEMPTED`` (75) — the clean re-runnable shutdown, distinct from
success and crash. Chaos events drive the same machinery
deterministically: ``--chaos "burst@8,poison@20,sigterm@40"`` for
serving, ``--chaos "corruptframe@5,abandon@9,sigterm@20"`` for
streaming (docs/SERVING.md and docs/STREAMING.md have the matrices).

Examples:
    python serve.py --platform cpu --num_requests 32 --size 96 128 \
        --iter_levels 12,6 --serve_batch_sizes 1,2
    python serve.py --restore_ckpt checkpoints/raft_nc_sintel \
        --chaos "burst@16" --queue_capacity 32
    python serve.py --platform cpu --stream --n_streams 3 \
        --frames_per_stream 6 --size 96 128 --stream_iters 8 \
        --chaos "corruptframe@7"
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import threading
import time

from raft_ncup_tpu.utils.knobs import knob_str


@contextlib.contextmanager
def _telemetry_export(args):
    """The periodic telemetry cadence for the run's duration: SLO
    burn-rate evaluation (ALWAYS — the budget controller's second
    degrade input and the report's verdict block are only truthful if
    the attached engine actually evaluates during the run, flags or
    not), plus bounded-JSONL snapshots (--telemetry_jsonl) and the
    atomically-rewritten healthz file (--healthz_file) when asked.

    Teardown order is the satellite contract: the PeriodicSnapshot's
    final tick (inner context) runs BEFORE the sink closes (outer), so
    the last report — the one describing the drained end state — can
    never hit a closed sink.
    """
    from raft_ncup_tpu.observability import (
        JsonlSink,
        PeriodicSnapshot,
        get_telemetry,
    )

    with contextlib.ExitStack() as stack:
        sink = None
        if args.telemetry_jsonl:
            sink = stack.enter_context(JsonlSink(args.telemetry_jsonl))
        stack.enter_context(PeriodicSnapshot(
            get_telemetry(), sink, args.telemetry_interval_s,
            healthz_path=args.healthz_file,
        ))
        yield


def _attach_observability(args, *, stream: bool):
    """Arm the consumer half on the process hub (docs/OBSERVABILITY.md):
    the declared SLO set (serve or stream — evaluated on the snapshot
    cadence, read by the budget controller and the healthz file) and
    the fault flight recorder. Returns the hub."""
    from raft_ncup_tpu.observability import (
        FlightRecorder,
        SloEngine,
        get_telemetry,
        serve_slos,
        stream_slos,
    )

    tel = get_telemetry()
    if args.flight_dir:
        tel.flight = FlightRecorder(args.flight_dir)
    specs = (
        stream_slos(args.stream_capacity,
                    window_scale=args.slo_window_scale)
        if stream
        else serve_slos(window_scale=args.slo_window_scale)
    )
    tel.slo = SloEngine(specs, tel)
    return tel


def build_parser() -> argparse.ArgumentParser:
    from raft_ncup_tpu.cli import (
        add_mesh_arg,
        add_model_args,
        add_platform_arg,
        add_serve_args,
        add_stream_args,
        str2bool as _str2bool,
    )

    parser = argparse.ArgumentParser(
        description="Serve RAFT / RAFT-NCUP flow over a synthetic "
        "open-loop request stream"
    )
    parser.add_argument("--restore_ckpt", default=None,
                        help="orbax run dir or torch .pth (default: "
                        "randomly initialized weights — the serving "
                        "machinery is shape-, not weight-, dependent)")
    parser.add_argument("--num_requests", type=int, default=32)
    parser.add_argument("--interval_ms", type=float, default=0.0,
                        help="steady inter-arrival gap (0 = as fast as "
                        "the submitting thread can go)")
    parser.add_argument("--size", type=int, nargs=2, default=[96, 128],
                        metavar=("H", "W"), help="request frame size")
    parser.add_argument("--burst_size", type=int, default=8,
                        help="requests per burst@N chaos event")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--style", default="smooth",
                        choices=["smooth", "rigid"],
                        help="synthetic traffic content generator")
    parser.add_argument("--chaos", default=None,
                        help="deterministic faults: comma-joined "
                        "burst@N / poison@N / sigterm@N (serving) or "
                        "corruptframe@N / abandon@N / burst@N / "
                        "sigterm@N (--stream) — resilience/chaos.py")
    parser.add_argument("--stream", action="store_true",
                        help="drive the streaming video engine "
                        "(raft_ncup_tpu/streaming/) instead of the "
                        "request server")
    parser.add_argument("--replica_socket", default=None, metavar="ADDR",
                        help="replica-server mode (raft_ncup_tpu/fleet/; "
                        "docs/FLEET.md): serve request/frame messages "
                        "over this wire address — a Unix-domain-socket "
                        "path or host:port for TCP "
                        "(length-prefixed "
                        "JSON header + raw ndarray frames) through the "
                        "FlowServer (+ StreamEngine) instead of "
                        "replaying synthetic traffic — the child "
                        "process a fleet ReplicaSupervisor spawns; "
                        "SIGTERM drains (healthz shows DRAINING before "
                        "the flush) and exits 75")
    parser.add_argument("--replica_index", type=int, default=0,
                        help="[--replica_socket] this replica's index "
                        "in the fleet topology (report + telemetry "
                        "correlation)")
    parser.add_argument("--replica_streams", type=_str2bool,
                        nargs="?", const=True, default=True,
                        help="[--replica_socket] also run a "
                        "StreamEngine so the replica serves stream "
                        "frames alongside one-shot requests "
                        "(false = request-only replica)")
    parser.add_argument("--report", action="store_true",
                        help="embed the full telemetry report "
                        "(observability.telemetry_report(): registry "
                        "snapshot, per-stage p50/p99, event accounting) "
                        "in the printed JSON")
    parser.add_argument("--telemetry_jsonl", default=None, metavar="PATH",
                        help="write periodic telemetry snapshots to this "
                        "bounded JSONL sink while serving "
                        "(observability/export.py)")
    parser.add_argument("--telemetry_interval_s", type=float, default=5.0,
                        help="snapshot cadence for --telemetry_jsonl / "
                        "--healthz_file (also the SLO burn-rate "
                        "evaluation cadence)")
    parser.add_argument("--healthz_file", default=None, metavar="PATH",
                        help="atomically rewrite this JSON file on the "
                        "telemetry cadence with per-subsystem health "
                        "states + SLO verdicts — the scrape surface a "
                        "fleet router polls (DRAINING rides the "
                        "SIGTERM/exit-75 contract; "
                        "docs/OBSERVABILITY.md)")
    parser.add_argument("--flight_dir",
                        default=knob_str(
                            "RAFT_NCUP_FLIGHT_DIR",
                            default="flight_recorder",
                        ),
                        help="fault flight-recorder directory: every "
                        "fault trigger (poison quarantine, anomaly "
                        "reset, SIGTERM drain, SLO page...) banks one "
                        "bounded atomic flight_<trigger>_<ts>.json "
                        "here ('' disables; scripts/postmortem.py "
                        "reads them)")
    parser.add_argument("--slo_window_scale", type=float, default=1.0,
                        help="scale the declared SLOs' 5m/1h burn-rate "
                        "windows (observability/slo.py) — e.g. 0.01 "
                        "for a seconds-scale demo/bench window")
    parser.add_argument("--n_streams", type=int, default=4,
                        help="[--stream] concurrent synthetic streams")
    parser.add_argument("--frames_per_stream", type=int, default=8,
                        help="[--stream] frames submitted per stream")
    add_serve_args(parser)
    add_stream_args(parser)
    add_mesh_arg(parser)
    add_model_args(parser)
    add_platform_arg(parser)
    return parser


def run_stream(args, model, variables) -> int:
    """--stream mode: replay a deterministic multi-stream schedule
    through the StreamEngine, drain, print one JSON report line."""
    from raft_ncup_tpu.cli import stream_config_from_args
    from raft_ncup_tpu.resilience import EXIT_PREEMPTED, PreemptionHandler
    from raft_ncup_tpu.resilience.chaos import ChaosSpec
    from raft_ncup_tpu.serving import nearest_rank_ms
    from raft_ncup_tpu.streaming import (
        StreamEngine,
        StreamTraffic,
        replay_streams,
    )
    from raft_ncup_tpu.utils.profiling import trace

    chaos = ChaosSpec.parse(args.chaos)
    if chaos.active:
        print(f"chaos: {chaos.render()}", file=sys.stderr)
    size_hw = (args.size[0], args.size[1])
    stream_cfg = stream_config_from_args(args, size_hw)

    tel = _attach_observability(args, stream=True)
    engine = StreamEngine(model, variables, stream_cfg)
    t0 = time.monotonic()
    compiled = engine.warmup()
    # Replica identity for the healthz file (docs/FLEET.md): the warmed
    # step set + mesh fingerprint a fleet router routes on.
    tel.identity.update({
        "mesh": engine.report()["mesh"],
        "warmed": [list(x) for x in engine.warmed],
    })
    print(
        f"warmup: {compiled} stream-step executables compiled in "
        f"{time.monotonic() - t0:.1f}s "
        f"(batch_sizes={stream_cfg.batch_sizes} "
        f"iters={stream_cfg.iters})",
        file=sys.stderr,
    )
    from raft_ncup_tpu.observability import startup_line

    print(startup_line(), file=sys.stderr)
    traffic = StreamTraffic(
        size_hw,
        args.n_streams,
        args.frames_per_stream,
        seed=args.seed,
        interval_s=args.interval_ms / 1000.0,
        burst_size=args.burst_size,
        chaos=chaos,
        style=args.style,
    )
    t0 = time.monotonic()
    with trace(args.trace_dir), _telemetry_export(args), \
            PreemptionHandler() as preempt:
        handles, interrupted = replay_streams(
            engine, traffic, preempt=preempt,
            sigterm_after=chaos.sigterm_after,
        )
        stats = engine.drain()
        if interrupted:
            # Fault trigger: the SIGTERM drain (exit 75), banked after
            # the flush so the dump describes the drained end state.
            tel.flight_dump(
                "preemption_drain",
                completed=stats.completed,
                shed_frames=stats.shed_frames,
            )
    wall = time.monotonic() - t0

    responses = [h.result(timeout=30.0) for h in handles]
    lat = [
        r.latency_s for r in responses if r.ok and r.latency_s is not None
    ]
    report = {
        "stream_frames": len(handles),
        "stream_ok": len(lat),
        "stream_wall_s": round(wall, 3),
        "stream_frames_per_sec": (
            round(stats.completed / wall, 3) if wall > 0 else None
        ),
        "stream_p50_ms": nearest_rank_ms(lat, 0.50),
        "stream_p99_ms": nearest_rank_ms(lat, 0.99),
        "interrupted": interrupted,
        "completed": stats.completed,
        "resets": stats.resets,
        "shed_streams": stats.shed_streams,
        "shed_frames": stats.shed_frames,
        "errors": stats.errors,
        **engine.report(),
        "slo": tel.slo.snapshot() if tel.slo is not None else None,
    }
    if args.report:
        from raft_ncup_tpu.inference.costs import get_cost_ledger
        from raft_ncup_tpu.observability import telemetry_report

        report["telemetry"] = telemetry_report()
        # The executable cost ledger (inference/costs.py): per-warmed-
        # executable flops/bytes/compile-time/memory-stats — host dicts
        # recorded at compile time, no sync to read.
        report["cost_ledger"] = get_cost_ledger().snapshot()
    print(json.dumps(report), flush=True)
    if interrupted:
        print(
            "stream: drained after signal — every admitted frame was "
            "flushed; exiting EXIT_PREEMPTED",
            file=sys.stderr,
        )
        return EXIT_PREEMPTED
    return 0


def run_replica(args, model, variables) -> int:
    """--replica_socket mode: one fleet replica (docs/FLEET.md).

    Serves ``request``/``frame`` messages from the router over a Unix
    domain socket through the existing FlowServer/StreamEngine — the
    replica IS the single-process serving tier, plus a wire. The
    service window runs under the runtime guards (0 recompiles after
    warmup, 0 implicit host transfers — the per-replica counters the
    fleet bench row asserts), the healthz file advertises the replica
    identity a router routes on (pid, mesh, warmed executable set), and
    SIGTERM runs the drain contract: healthz shows DRAINING *before*
    the flush, everything admitted is flushed, exit 75.
    """
    import socket as socket_mod
    from concurrent.futures import ThreadPoolExecutor

    from raft_ncup_tpu.analysis.guards import (
        GuardStats,
        RecompileWatchdog,
        forbid_host_transfers,
    )
    from raft_ncup_tpu.cli import (
        serve_config_from_args,
        stream_config_from_args,
    )
    from raft_ncup_tpu.fleet.wire import Transport, recv_msg, send_msg
    from raft_ncup_tpu.observability import write_healthz
    from raft_ncup_tpu.resilience import EXIT_PREEMPTED, PreemptionHandler
    from raft_ncup_tpu.serving import FlowServer

    size_hw = (args.size[0], args.size[1])
    serve_cfg = serve_config_from_args(args)
    tel = _attach_observability(args, stream=False)
    server = FlowServer(model, variables, serve_cfg)
    engine = None
    if args.replica_streams:
        from raft_ncup_tpu.observability import (
            SloEngine,
            serve_slos,
            stream_slos,
        )
        from raft_ncup_tpu.streaming import StreamEngine

        # A replica serving BOTH tiers declares BOTH SLO sets: a
        # replica that sheds every stream frame while its serve tier is
        # healthy must page (and read degraded in healthz), or the
        # router keeps homing streams on it.
        tel.slo = SloEngine(
            serve_slos(window_scale=args.slo_window_scale)
            + stream_slos(args.stream_capacity,
                          window_scale=args.slo_window_scale),
            tel,
        )
        stream_cfg = stream_config_from_args(args, size_hw)
        engine = StreamEngine(model, variables, stream_cfg)
    t0 = time.monotonic()
    compiled = server.warmup(size_hw)
    if engine is not None:
        compiled += engine.warmup()
    # The replica identity the healthz file advertises (write_healthz
    # merges Telemetry.identity): the warmed (shape, batch, iters)
    # executable set is what the router's shape-aware routing reads.
    tel.identity.update({
        "replica": args.replica_index,
        "mesh": server.report()["mesh"],
        "warmed": [list(x) for x in server.warmed],
    })
    if engine is not None:
        tel.identity["stream_warmed"] = [list(x) for x in engine.warmed]
    print(
        f"replica {args.replica_index}: {compiled} executables compiled "
        f"in {time.monotonic() - t0:.1f}s; serving on "
        f"{args.replica_socket}",
        file=sys.stderr,
    )
    from raft_ncup_tpu.observability import startup_line

    print(startup_line(), file=sys.stderr)

    # The address string decides the socket family (UDS path vs
    # host:port) — the same string the FleetConfig argv carried, so a
    # topology moves to TCP without touching the replica code path.
    transport = Transport.parse(args.replica_socket)
    lsock = transport.listen(16)
    lsock.settimeout(0.1)

    pool = ThreadPoolExecutor(
        max_workers=32, thread_name_prefix="replica-respond"
    )
    conns: list = []

    def respond(conn, send_lock, rid, handle, t_recv, trace_id) -> None:
        """Wait for one request's terminal response and wire it back
        (each handle completes exactly once; the drain flush completes
        every admitted handle, so the bounded wait only trips if the
        serving tier itself wedged)."""
        try:
            r = handle.result(timeout=600.0)
        except TimeoutError:
            r = None
        header = {
            "kind": "response",
            "id": rid,
            "status": "error" if r is None else r.status,
            "iters": None if r is None else r.iters,
            "latency_s": None if r is None else r.latency_s,
            "retry_after_s": None if r is None else r.retry_after_s,
            "detail": "replica response timeout" if r is None else r.detail,
            # Per-hop timing stamps on THIS replica's monotonic clock
            # (receive -> done); the router translates them through the
            # handshake offset into fleet_hop_wire/replica/return_ms.
            # Optional fields: an old router just ignores them.
            "t_recv_s": t_recv,
            "t_done_s": time.monotonic(),
        }
        if trace_id is not None:
            header["trace"] = {"trace_id": trace_id}
        arrays = (r.flow,) if (r is not None and r.flow is not None) else ()
        try:
            with send_lock:
                send_msg(conn, header, arrays)
        except OSError:
            # The router hung up (death detection already failed the
            # request over on its side); nothing to deliver to.
            tel.inc("replica_response_undeliverable_total")

    def serve_conn(conn) -> None:
        from raft_ncup_tpu.observability.spans import TraceContext

        send_lock = threading.Lock()
        try:
            while True:
                msg = recv_msg(conn)
                if msg is None:
                    break
                t_recv = time.monotonic()
                header, arrays = msg
                kind = header.get("kind")
                if kind == "ping":
                    # Clock handshake: echo the router's t0 and stamp
                    # our monotonic clock, so the router can estimate
                    # replica_mono - router_mono (rtt-halved).
                    with send_lock:
                        send_msg(conn, {
                            "kind": "pong", "pid": os.getpid(),
                            "t0": header.get("t0"),
                            "t_mono": time.monotonic(),
                        })
                    continue
                if kind == "set_telemetry":
                    # Bench's fleet telemetry-overhead window: flip the
                    # hub in place on the warm replica (the same
                    # Telemetry.enabled bool the serve row flips
                    # in-process). Guards and product stats keep
                    # counting either way.
                    tel.enabled = bool(header.get("enabled", True))
                    with send_lock:
                        send_msg(conn, {
                            "kind": "telemetry_ack",
                            "enabled": tel.enabled,
                            "replica": args.replica_index,
                        })
                    continue
                rid = int(header.get("id", -1))
                # Adopt the inbound trace context (an OPTIONAL header
                # field — frames without it parse identically): the
                # replica's admission/batch/device spans then carry the
                # router's trace_id, and the measured wire hop lands as
                # a replica-side span under the same trace.
                ctx = TraceContext.from_wire(header.get("trace"))
                tid = None
                if ctx is not None:
                    tid = ctx.trace_id
                    if ctx.sent_s is not None:
                        tel.observe_ms(
                            "fleet_wire_hop",
                            max(0.0, (t_recv - (ctx.sent_s
                                                + ctx.clock_offset_s))
                                * 1e3),
                            trace_id=tid, request_id=rid,
                            parent_span_id=ctx.span_id,
                            replica=args.replica_index,
                        )
                if kind == "request" and len(arrays) == 2:
                    handle = server.submit(
                        arrays[0], arrays[1],
                        deadline_s=header.get("deadline_s"),
                        request_id=rid,
                        trace_id=tid,
                    )
                elif kind == "frame" and len(arrays) == 2:
                    if engine is None:
                        with send_lock:
                            send_msg(conn, {
                                "kind": "response", "id": rid,
                                "status": "rejected",
                                "detail": "request-only replica "
                                "(replica_streams=false)",
                            })
                        continue
                    handle = engine.submit(
                        str(header.get("stream_id")),
                        arrays[0], arrays[1],
                        frame_index=header.get("frame_index"),
                        request_id=rid,
                        trace_id=tid,
                    )
                else:
                    with send_lock:
                        send_msg(conn, {
                            "kind": "response", "id": rid,
                            "status": "rejected",
                            "detail": f"bad message kind {kind!r}",
                        })
                    continue
                pool.submit(respond, conn, send_lock, rid, handle,
                            t_recv, tid)
        except (ConnectionError, OSError, ValueError) as e:
            print(f"replica connection dropped: {e!r}", file=sys.stderr)
        finally:
            try:
                conn.close()
            except OSError:
                pass

    stats = GuardStats()
    interrupted = False
    # Guards arm AFTER warmup: every compile from here on is a
    # steady-state recompile, every implicit pull a leak — the
    # per-replica counters the fleet bench row requires to be 0.
    with _telemetry_export(args), PreemptionHandler() as preempt, \
            RecompileWatchdog() as wd, \
            forbid_host_transfers(stats, raise_on_violation=False):
        while not preempt.requested:
            try:
                conn, _ = lsock.accept()
            except socket_mod.timeout:
                continue
            except OSError:
                break
            conns.append(conn)
            threading.Thread(
                target=serve_conn, args=(conn,),
                name="replica-conn", daemon=True,
            ).start()
        interrupted = preempt.requested
        # Drain contract: DRAINING must be visible to a healthz poller
        # BEFORE the flush — the router stops routing here while the
        # in-flight work completes. The explicit write makes the
        # ordering independent of the snapshot cadence.
        server.health.draining("sigterm")
        if engine is not None:
            engine.health.draining("sigterm")
        if args.healthz_file:
            write_healthz(args.healthz_file, tel,
                          interval_s=args.telemetry_interval_s)
        sstats = server.drain()
        estats = engine.drain() if engine is not None else None
        if interrupted:
            tel.flight_dump(
                "preemption_drain",
                replica=args.replica_index,
                completed=sstats.completed,
                shed=sstats.shed,
            )
        # Every handle is now terminal; let the responders flush.
        pool.shutdown(wait=True)
        # Orderly close of every connection still open: peers get EOF
        # from the drain, not from process exit.
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass
    lsock.close()
    transport.cleanup()

    report = {
        "replica": args.replica_index,
        "interrupted": interrupted,
        "recompiles": wd.count,
        "host_transfers": stats.host_transfers,
        "completed": sstats.completed,
        "shed": sstats.shed,
        "timeouts": sstats.timeouts,
        "rejected": sstats.rejected,
        "errors": sstats.errors,
        **server.report(),
        "slo": tel.slo.snapshot() if tel.slo is not None else None,
    }
    if estats is not None:
        report["stream_completed"] = estats.completed
        report["stream_resets"] = estats.resets
        report["stream_shed_frames"] = estats.shed_frames
        report["stream_errors"] = estats.errors
        report["stream_report"] = engine.report()
    if args.report:
        from raft_ncup_tpu.inference.costs import get_cost_ledger
        from raft_ncup_tpu.observability import telemetry_report

        report["telemetry"] = telemetry_report()
        # The executable cost ledger (inference/costs.py): per-warmed-
        # executable flops/bytes/compile-time/memory-stats — host dicts
        # recorded at compile time, no sync to read.
        report["cost_ledger"] = get_cost_ledger().snapshot()
    print(json.dumps(report), flush=True)
    if interrupted:
        print(
            f"replica {args.replica_index}: drained after signal — "
            "everything admitted was flushed; exiting EXIT_PREEMPTED",
            file=sys.stderr,
        )
        return EXIT_PREEMPTED
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from raft_ncup_tpu.cli import apply_platform

    apply_platform(args)
    from raft_ncup_tpu.utils.runtime import enable_compilation_cache

    enable_compilation_cache()

    from evaluate import load_variables
    from raft_ncup_tpu.cli import model_config_from_args, serve_config_from_args
    from raft_ncup_tpu.models.raft import RAFT
    from raft_ncup_tpu.resilience import EXIT_PREEMPTED, PreemptionHandler
    from raft_ncup_tpu.resilience.chaos import ChaosSpec
    from raft_ncup_tpu.serving import (
        FlowServer,
        SyntheticTraffic,
        nearest_rank_ms,
        replay,
    )
    from raft_ncup_tpu.utils.profiling import trace

    model_cfg = model_config_from_args(args)
    model = RAFT(model_cfg)
    variables = load_variables(model, model_cfg, args.restore_ckpt)
    if args.replica_socket:
        return run_replica(args, model, variables)
    if args.stream:
        return run_stream(args, model, variables)

    serve_cfg = serve_config_from_args(args)
    chaos = ChaosSpec.parse(args.chaos)
    if chaos.active:
        print(f"chaos: {chaos.render()}", file=sys.stderr)

    size_hw = (args.size[0], args.size[1])

    tel = _attach_observability(args, stream=False)
    server = FlowServer(model, variables, serve_cfg)
    t0 = time.monotonic()
    compiled = server.warmup(size_hw)
    # Replica identity for the healthz file (docs/FLEET.md): the warmed
    # (shape, batch, iters) executable set + mesh fingerprint a fleet
    # router's shape-aware routing reads.
    tel.identity.update({
        "mesh": server.report()["mesh"],
        "warmed": [list(x) for x in server.warmed],
    })
    print(
        f"warmup: {compiled} executables compiled in "
        f"{time.monotonic() - t0:.1f}s "
        f"(batch_sizes={serve_cfg.batch_sizes} "
        f"iter_levels={serve_cfg.iter_levels})",
        file=sys.stderr,
    )
    from raft_ncup_tpu.observability import startup_line

    print(startup_line(), file=sys.stderr)

    traffic = SyntheticTraffic(
        size_hw,
        args.num_requests,
        seed=args.seed,
        interval_s=args.interval_ms / 1000.0,
        burst_size=args.burst_size,
        chaos=chaos,
        style=args.style,
    )
    t0 = time.monotonic()
    with trace(args.trace_dir), _telemetry_export(args), \
            PreemptionHandler() as preempt:
        handles, interrupted = replay(
            server, traffic, preempt=preempt,
            sigterm_after=chaos.sigterm_after,
        )
        stats = server.drain()
        if interrupted:
            # Fault trigger: the SIGTERM drain (exit 75), banked after
            # the flush so the dump describes the drained end state.
            tel.flight_dump(
                "preemption_drain",
                completed=stats.completed, shed=stats.shed,
            )
    wall = time.monotonic() - t0

    responses = [h.result(timeout=30.0) for h in handles]
    lat = [
        r.latency_s for r in responses if r.ok and r.latency_s is not None
    ]

    report = {
        "serve_requests": len(handles),
        "serve_ok": len(lat),
        "serve_wall_s": round(wall, 3),
        "serve_pairs_per_sec": (
            round(stats.completed / wall, 3) if wall > 0 else None
        ),
        "serve_p50_ms": nearest_rank_ms(lat, 0.50),
        "serve_p99_ms": nearest_rank_ms(lat, 0.99),
        "interrupted": interrupted,
        "completed": stats.completed,
        "shed": stats.shed,
        "timeouts": stats.timeouts,
        "rejected": stats.rejected,
        "errors": stats.errors,
        **server.report(),
        "slo": tel.slo.snapshot() if tel.slo is not None else None,
    }
    if args.report:
        from raft_ncup_tpu.inference.costs import get_cost_ledger
        from raft_ncup_tpu.observability import telemetry_report

        report["telemetry"] = telemetry_report()
        # The executable cost ledger (inference/costs.py): per-warmed-
        # executable flops/bytes/compile-time/memory-stats — host dicts
        # recorded at compile time, no sync to read.
        report["cost_ledger"] = get_cost_ledger().snapshot()
    print(json.dumps(report), flush=True)
    if interrupted:
        print(
            "serve: drained after signal — everything admitted was "
            "flushed; exiting EXIT_PREEMPTED",
            file=sys.stderr,
        )
        return EXIT_PREEMPTED
    return 0


if __name__ == "__main__":
    sys.exit(main())
