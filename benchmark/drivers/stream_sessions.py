"""Driver ``stream_sessions``: the program's ``StreamEngine`` in this process,
under a closed loop of ``players`` threads, each playing video sessions.

A session is one stream id and one clip of the seed's pool, played pair by
pair from its first frame: frames (t, t+1), then (t+1, t+2), so consecutive
pairs share a frame. The player submits a pair, waits for the answer, checks
it and submits the next at once. The session's first pair starts cold (the
engine knows no such stream); every later one starts from the forward splat
of the answer before it, which the engine keeps in its device slot table.
When the session's pairs are spent the player closes the stream and opens a
new id at once, with a new clip and a new length.

Set-up opens every player's session and plays its cold pair and one warm
pair (which also warms the one program), then gives each session a seeded
number of pairs left, so that session ends are spread over the window as on
a server that has run for a while. The window opens with every player's next
pair already queued (``pause()`` / ``resume()``); after ``seconds`` no player
starts a pair, and the window closes when the last answer is in. The rate is
all ``ok`` answers over all that time. An answer that is not ``ok`` (shed,
rejected, error, anomaly reset) counts as failed.

``check`` holds the window's counters to what the players did (every cold
start is a session's first pair and the reverse, nothing shed or reset, the
padded rows those the report shows) and then plays ``check_sessions`` fresh sessions of ``check_pairs``
pairs through the same engine, executable and slot table, in batches filled
with pool sessions, against ``reference_session`` on the same frames. Held
to limits: over the WARM answers the worst median (tightly) and the worst mean
(loosely) of the endpoint gap over an answer's pixels, see
``_gaps_to_reference``; the same answers against the reference with its warm
start dropped must read over the limit, or the comparison cannot see the
mechanism.
"""

from __future__ import annotations

import threading
import time

import cv2
import jax
import numpy as np

from benchmark import traffic_gen
from benchmark.checks import flops_info
from benchmark.harness import NoResult, compared, emit
from benchmark.program import build_model
from benchmark.reference.raft import Reference
from benchmark.reference.raft_stream import reference_session
from benchmark.trace_reduce import SPAN_PREFIX

WAIT_S = 300.0  # for an answer or a barrier: a hang becomes a failure

COUNTERS = {  # the engine's counters the window is held to, by what they count
    "pairs": "stream_frames_accepted_total",
    "completed": "stream_frames_completed_total",
    "cold": "stream_frames_cold_start_total",
    "padded_rows": "stream_batch_padded_rows_total",
    "batches": "stream_batches_total",
    "opened": "stream_streams_opened_total",
    "closed": "stream_streams_closed_total",
    "resets": "stream_slots_reset_total",
    "shed_frames": "stream_frames_shed_total",
    "shed_streams": "stream_streams_shed_total",
    "evicted": "stream_streams_evicted_total",
    "errors": "stream_frames_error_total",
}


# ---------------------------------------------------------------------- clips


def make_clip(rng: np.random.Generator, hw, frames: int, max_flow_px: float) -> list:
    """``frames`` uint8 (H, W, 3) frames: the pair of ``traffic_gen.make_pair``
    and then each frame the one before it warped by a fresh smooth flow, as
    that recipe warps its second frame."""
    h, w = hw
    pair = traffic_gen.make_pair(rng, hw, max_flow_px)
    clip = [pair["image1"], pair["image2"]]
    xx, yy = np.meshgrid(np.arange(w, dtype=np.float32), np.arange(h, dtype=np.float32))
    while len(clip) < frames:
        flow = (traffic_gen._smooth_noise(rng, hw, 32, 2) * (max_flow_px / 2.0)).astype(np.float32)
        clip.append(cv2.remap(
            clip[-1], xx - flow[..., 0], yy - flow[..., 1], cv2.INTER_LINEAR,
            borderMode=cv2.BORDER_REFLECT,
        ))
    return clip[:frames]


def make_clips(traffic: dict, seed: int) -> list:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x636C6970]))
    return [
        make_clip(rng, tuple(traffic["native_hw"]), int(traffic["clip_frames"]),
                  float(traffic["max_flow_px"]))
        for _ in range(int(traffic["clips"]))
    ]


# -------------------------------------------------------------------- players


class Player:
    """One closed loop: a session at a time, a pair at a time."""

    def __init__(self, state: dict, k: int):
        self.state, self.k = state, k
        self.rng = np.random.default_rng(np.random.SeedSequence([state["cell"].seed, 0x5E55, k]))
        self.sessions = 0  # opened so far: the next stream id's number
        self.ok = self.bad = self.begun = self.ended = 0
        self.open_session(first=True)

    def open_session(self, first: bool = False) -> None:
        """A new stream id, clip and length. Set-up's sessions are mid-way:
        ``left`` of their pairs are still to play after set-up's two."""
        t = self.state["traffic"]
        lo, hi = t["session_pairs"]
        length = int(self.rng.integers(lo, hi + 1))
        self.clip = self.state["clips"][int(self.rng.integers(len(self.state["clips"])))]
        if length >= len(self.clip):
            raise NoResult(f"a session of {length} pairs needs a clip of {length + 1} frames")
        self.stream_id = f"player{self.k}-session{self.sessions}"
        self.sessions += 1
        if first:
            self.left = min(int(self.rng.integers(1, length + 1)), length - 2) + 2
        else:
            self.left = length
        self.at = length - self.left  # index of the next pair in the clip
        self.fresh = True  # the next pair is the session's first

    def play_pair(self, on_submitted=None) -> None:
        """Submit the session's next pair, wait, count; at the session's end
        close the stream and open the next."""
        engine = self.state["engine"]
        self.begun += int(self.fresh)  # a session's first pair: the cold one
        self.fresh = False
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "player_wait"):
            handle = engine.submit(self.stream_id, self.clip[self.at], self.clip[self.at + 1])
            if on_submitted is not None:
                on_submitted()
            try:
                resp = handle.result(WAIT_S)
            except TimeoutError:
                resp = None
        good = bool(
            resp is not None and resp.ok and resp.flow is not None
            and resp.flow.shape == self.clip[self.at].shape[:2] + (2,)
        )
        self.ok += int(good)
        self.bad += int(not good)
        self.at += 1
        self.left -= 1
        if self.left == 0:
            engine.close_stream(self.stream_id)
            self.ended += 1
            self.open_session()


def _counters(engine) -> dict:
    got = engine.report()["counters"]
    return {k: int(got[name]) for k, name in COUNTERS.items()}


def _settled_counters(state) -> dict:
    """The counters once the engine has booked what the players already
    know: a handle completes a moment before its frame is counted and its
    closed stream released (the drain worker's next lines)."""
    ended = sum(p.ended for p in state["players"])
    deadline = time.perf_counter() + 5.0
    while True:
        c = _counters(state["engine"])
        booked = c["completed"] + c["resets"] + c["errors"] == c["pairs"]
        if (booked and c["closed"] >= ended) or time.perf_counter() > deadline:
            return c
        time.sleep(0.005)


# --------------------------------------------------------------------- driver


def setup(cell) -> dict:
    from raft_ncup_tpu.config import StreamConfig
    from raft_ncup_tpu.observability import Telemetry
    from raft_ncup_tpu.streaming import StreamEngine

    t, s = cell.traffic, cell.config["stream"]
    ref = Reference(cell.config["model"])
    variables = ref.init_variables(cell.seed)
    telemetry = Telemetry()
    engine = StreamEngine(
        build_model(cell.config["model"]), variables,
        StreamConfig(
            capacity=int(s["capacity"]), frame_hw=tuple(s["frame_hw"]), iters=int(s["iters"]),
            batch_sizes=tuple(s["batch_sizes"]), max_frame_gap=int(s["max_frame_gap"]),
            carry_net=bool(s["carry_net"]), queue_capacity=int(t["queue_capacity"]),
        ),
        telemetry=telemetry,
    )
    state = {
        "cell": cell, "traffic": t, "stream": s, "ref": ref, "variables": variables,
        "engine": engine, "telemetry": telemetry,
    }
    try:
        if "counters" not in engine.report():
            raise NoResult("this program's StreamEngine.report() has no 'counters'")
        engine.warmup()
        state["clips"] = make_clips(t, cell.seed)
        state["players"] = [Player(state, k) for k in range(int(t["players"]))]
        # every session's cold pair, then one warm pair: the host path's first
        # use and the one program's first real batches
        for _ in range(2):
            _round(state["players"])
        bad = sum(p.bad for p in state["players"])
        if bad:
            raise NoResult(f"{bad} of set-up's pairs were not answered ok")
    except BaseException:
        close(state)
        raise
    return state


def _round(players: list) -> None:
    """One pair of every player, all in the queue before any is dispatched."""
    engine = players[0].state["engine"]
    queued = threading.Barrier(len(players) + 1, timeout=WAIT_S)
    threads = [
        threading.Thread(target=p.play_pair, args=(queued.wait,), name=f"bench-player-{p.k}")
        for p in players
    ]
    engine.pause()
    for th in threads:
        th.start()
    queued.wait()
    engine.resume()
    for th in threads:
        th.join()


def _closed_loop(state, seconds: float) -> float:
    """Every player plays at least one pair; none starts one after
    ``seconds``. Returns the time until the last answer was in."""
    players, engine = state["players"], state["engine"]
    queued = threading.Barrier(len(players) + 1, timeout=WAIT_S)
    engine.pause()
    t0 = time.perf_counter()

    def loop(p: Player) -> None:
        p.play_pair(queued.wait)
        while time.perf_counter() - t0 < seconds:
            p.play_pair()

    threads = [threading.Thread(target=loop, args=(p,), name=f"bench-player-{p.k}")
               for p in players]
    for th in threads:
        th.start()
    queued.wait()  # every player's first pair is in the queue
    engine.resume()
    for th in threads:
        th.join()
    return time.perf_counter() - t0


def run(state, seconds: float) -> dict:
    t, cell, engine = state["traffic"], state["cell"], state["engine"]
    players = state["players"]
    state["telemetry"].registry.reset()  # the stage histograms of the window alone
    before = _settled_counters(state)
    mine = {k: sum(getattr(p, k) for p in players) for k in ("ok", "bad", "begun", "ended")}
    window_s = _closed_loop(state, seconds)
    after = _settled_counters(state)
    report = engine.report()
    counters = {k: after[k] - before[k] for k in COUNTERS}
    did = {k: sum(getattr(p, k) for p in players) - v for k, v in mine.items()}
    done, failed = did["ok"], did["bad"]
    info = flops_info(
        cell.config["model"], t["native_hw"], int(state["stream"]["iters"]), done, window_s
    )
    return {
        "window_s": window_s, "attempted": done + failed, "failed": failed,
        "end_to_end": {"pairs_per_s": done / window_s},
        "pairs": done, "generator_lateness_s": 0.0,
        "sessions_ended": did["ended"], "sessions_begun": did["begun"], "counters": counters,
        "batch_rows": max(state["stream"]["batch_sizes"]),
        "occupancy": report["occupancy"], "peak_occupancy": report["peak_occupancy"],
        "executable_memory": report["executable_memory"],
        # the counters under the engine's names, as the window's deltas: what
        # the per-layer readers divide
        "report": {
            "stages": report["stages"],
            "counters": {COUNTERS[k]: v for k, v in counters.items()},
        },
        "executables": report["executables"], **info,
    }


def _window_rows(window: dict) -> list:
    """The engine's counters against what the players did in the window."""
    c = window["counters"]
    return [
        compared("completed_gap", abs(c["completed"] - window["attempted"]), 0),
        compared("accepted_gap", abs(c["pairs"] - window["attempted"]), 0),
        # a warm pair served cold is a wrong answer, not a slow one
        compared("cold_starts_gap_to_sessions_begun", abs(c["cold"] - window["sessions_begun"]), 0),
        compared("streams_opened_gap", abs(c["opened"] - window["sessions_begun"]), 0),
        compared("streams_closed_gap", abs(c["closed"] - window["sessions_ended"]), 0),
        compared(
            "resets_sheds_evictions_errors",
            c["resets"] + c["shed_frames"] + c["shed_streams"] + c["evicted"] + c["errors"], 0,
        ),
        # the rows the report shows are the rows dispatched: whole batches of
        # the window's pairs and the padding (none while 3 x batch players
        # keep every batch full; ``stream_padded_rows_pct`` reads the share)
        compared(
            "rows_gap_to_batches",
            abs(c["batches"] * window["batch_rows"] - c["pairs"] - c["padded_rows"]), 0,
        ),
    ]


def _check_frames(state) -> list:
    """``check_sessions`` runs of ``check_pairs`` + 1 consecutive frames, each
    from another clip at a seeded offset."""
    t, cell, clips = state["traffic"], state["cell"], state["clips"]
    n_pairs = int(t["check_pairs"])
    rng = np.random.default_rng(np.random.SeedSequence([cell.seed, 0xC4EC]))
    picks = traffic_gen.sample_indices(cell.seed, len(clips), int(t["check_sessions"]))
    out = []
    for i in picks:
        at = int(rng.integers(0, len(clips[i]) - n_pairs))
        out.append(clips[i][at : at + n_pairs + 1])
    return out


def _play_check_sessions(state, sessions: list) -> list:
    """The check sessions through the live engine, pair by pair, each batch
    filled up with the next pairs of pool sessions (the window's own
    players): ``[session][pair] -> flow or None``."""
    engine, players = state["engine"], state["players"]
    batch = max(state["stream"]["batch_sizes"])
    fillers = players[: max(0, batch - len(sessions))]
    tag = f"check{state.setdefault('checks_played', 0)}"
    state["checks_played"] += 1
    flows = [[] for _ in sessions]
    for j in range(len(sessions[0]) - 1):
        queued = threading.Barrier(len(fillers) + 1, timeout=WAIT_S)
        threads = [threading.Thread(target=p.play_pair, args=(queued.wait,)) for p in fillers]
        engine.pause()
        handles = [
            engine.submit(f"{tag}-session{n}", frames[j], frames[j + 1])
            for n, frames in enumerate(sessions)
        ]
        for th in threads:
            th.start()
        queued.wait()
        engine.resume()
        for n, handle in enumerate(handles):
            resp = handle.result(WAIT_S)
            flows[n].append(np.asarray(resp.flow, np.float32) if resp.ok else None)
        for th in threads:
            th.join()
    for n in range(len(sessions)):
        engine.close_stream(f"{tag}-session{n}")
    return flows


def _gap(a, b) -> dict:
    """The endpoint distance (px) between two flows of one pair, over its
    pixels: mean, median, 90th percentile. Not-a-number for a malformed one."""
    if a is None or a.shape != b.shape or not np.isfinite(a).all():
        return {"mean": float("nan"), "median": float("nan"), "p90": float("nan")}
    epe = np.sqrt(((a - b) ** 2).sum(-1))
    return {"mean": float(epe.mean()), "median": float(np.median(epe)),
            "p90": float(np.percentile(epe, 90))}


def _worst(gaps: list, stat: str) -> float:
    return float(np.max([g[stat] for g in gaps]))


def _smallest(gaps: list, stat: str) -> float:
    return float(np.min([g[stat] for g in gaps]))


def _gaps_to_reference(state) -> list:
    """Why two numbers. The nearest fill is discontinuous: where two landings
    are all but equally near a grid cell, the program's and the reference's
    previous flows (1e-5 px apart) pick different ones, that cell starts from
    another vector and a blob of the answer moves by whole pixels (PERF.md
    section 6: 3 of 48 warm answers on the chip). The mean over the image sees
    the blob; the median over its pixels does not, and every lower precision
    moves every pixel. So the median is held tightly and the mean loosely."""
    cell, iters = state["cell"], int(state["stream"]["iters"])
    sessions = _check_frames(state)
    got = _play_check_sessions(state, sessions)
    warm, cold, dropped, mags = [], [], [], []
    for frames, flows in zip(sessions, got):
        want = reference_session(state["ref"], state["variables"], frames, iters)
        # the warm pairs again, every one cold (the first pair is cold anyway)
        no_warm = reference_session(state["ref"], state["variables"], frames[1:], iters, warm_start=False)
        cold.append(_gap(flows[0], want[0]))
        warm += [_gap(f, w) for f, w in zip(flows[1:], want[1:])]
        dropped += [_gap(f, w) for f, w in zip(flows[1:], no_warm)]
        mags += [float(np.abs(w).mean()) for w in want]
    emit({
        "phase": "reference", "check_sessions": len(sessions), "pairs_each": len(sessions[0]) - 1,
        "reference_mean_abs_flow_px": float(np.mean(mags)),
        "flow_gap_warm_px": warm, "flow_gap_cold_px": cold,
        "flow_gap_to_reference_without_warm_start_px": dropped,
    })
    median_limit, mean_limit = cell.limit("flow_gap_median_px"), cell.limit("flow_gap_mean_px")
    return [
        compared("flow_gap_median_px", _worst(warm, "median"), median_limit),
        compared("flow_gap_mean_px", _worst(warm, "mean"), mean_limit),
        compared("flow_gap_cold_median_px", _worst(cold, "median"), median_limit),
        # the same answers against the reference that drops the mechanism
        # must NOT pass: the smallest of them, negated, held under -limit
        compared("warm_start_dropped_gap_px_negated", -_smallest(dropped, "mean"), -mean_limit),
    ]


def check(state, window: dict) -> list:
    return _window_rows(window) + _gaps_to_reference(state)


def reading(cell, seconds: float) -> list:
    """The program's reading of the numbers a limit is set from, for
    ``readings.py``: a short window at the cell's own load, then the check."""
    state = setup(cell)
    try:
        window = run(state, seconds)
        return [compared("failed", window["failed"], 0)] + check(state, window)
    finally:
        close(state)


def control(cell) -> list:
    """The controls' readings of the numbers ``check`` compares, the reference
    standing in the program's place on the frames ``check`` samples for this
    seed: at the configuration's control precision (``high``; the smallest over
    its warm pairs, where ``check`` holds the largest of its own to the limit),
    and at ``highest`` with the warm start dropped."""
    t, iters = cell.traffic, int(cell.config["stream"]["iters"])
    ref = Reference(cell.config["model"])
    low = Reference(cell.config["model"], precision=cell.config["control"]["reference_precision"])
    variables = ref.init_variables(cell.seed)
    state = {"cell": cell, "traffic": t, "clips": make_clips(t, cell.seed)}
    high, dropped = [], []
    for frames in _check_frames(state):
        want = reference_session(ref, variables, frames, iters)
        ctrl = reference_session(low, variables, frames, iters)
        no_warm = reference_session(ref, variables, frames[1:], iters, warm_start=False)
        high += [_gap(a, b) for a, b in zip(ctrl[1:], want[1:])]
        dropped += [_gap(a, b) for a, b in zip(no_warm, want[1:])]
    emit({"phase": "control", "flow_gap_warm_px": high, "warm_start_dropped_px": dropped})
    return [
        compared("flow_gap_median_px", _smallest(high, "median"), cell.limit("flow_gap_median_px")),
        compared("flow_gap_mean_px", _smallest(high, "mean"), cell.limit("flow_gap_mean_px")),
        compared("warm_start_dropped_gap_px", _smallest(dropped, "mean"), cell.limit("flow_gap_mean_px")),
    ]


def close(state) -> None:
    state["engine"].drain(timeout=120)
