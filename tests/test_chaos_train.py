"""End-to-end resilience: the chaos harness (resilience/chaos.py) driven
through the REAL pipeline — synthetic dataset → FlowLoader (+retry) →
DevicePrefetcher → sentinel-guarded jitted step → Logger → orbax — via
``train.main``. The acceptance contracts of docs/RESILIENCE.md:

- injected NaN batch ⇒ that step is a skip-update, the run continues,
  skip counters land in log.txt;
- K consecutive bad steps ⇒ halt, rollback to the last good checkpoint,
  EXIT_DIVERGED;
- SIGTERM mid-run ⇒ one atomic checkpoint, EXIT_PREEMPTED, and a resumed
  run whose loss trajectory is bitwise-identical to an uninterrupted one;
- injected IOError ⇒ retried with backoff, accounted, run unaffected;
- all of it under ``--strict_guards``: 0 steady-state recompiles, 0
  forbidden host transfers.

The in-process tests use chaos's step-pinned self-SIGTERM (the same
handler path as an external kill, deterministic); the slow test spawns a
real child train process and SIGTERMs it from outside.
"""

from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
import time

import pytest

from raft_ncup_tpu.resilience import EXIT_DIVERGED, EXIT_PREEMPTED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _args(tmp_path, name, extra):
    return [
        "--name", name,
        "--model", "raft",
        "--small",
        "--stage", "chairs",
        "--image_size", "16", "32",
        "--batch_size", "2",
        "--iters", "1",
        "--sum_freq", "1",
        "--val_freq", "100",
        "--synthetic_ok",
        "--num_workers", "1",
        "--data_parallel", "1",
        "--checkpoint_dir", str(tmp_path / "checkpoints"),
        "--root_chairs", str(tmp_path / "missing"),
    ] + extra


def _run(tmp_path, name, extra):
    import train as train_driver

    return train_driver.main(_args(tmp_path, name, extra))


def _log(tmp_path, name) -> str:
    return (tmp_path / "checkpoints" / name / "log.txt").read_text()


def _flight_dumps(tmp_path, name) -> list:
    """Flight-recorder dumps a run left under its run dir (sorted)."""
    d = tmp_path / "checkpoints" / name / "flight"
    return sorted(os.listdir(d)) if d.exists() else []


def _postmortem(argv):
    """Run scripts/postmortem.py in-process; returns its exit code."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "postmortem", os.path.join(REPO, "scripts", "postmortem.py")
    )
    pm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm)
    return pm.main(argv)


def _trajectory(log: str) -> dict:
    """step -> the summary line's metric portion. The it/s field is
    wall-clock (never reproducible); everything after it — the loss and
    metric means printed at 1e-4 — must be."""
    out = {}
    for line in log.splitlines():
        m = re.match(r"\[\s*(\d+) .*it/s\](.*)$", line)
        if m:
            out[int(m.group(1))] = m.group(2)
    return out


# Tier-2: ~80s (two full in-process train runs plus a resume). The
# SIGTERM-save path itself stays tier-1 via the cheaper preemption
# tests; the bitwise resumed-trajectory pin runs in the unfiltered
# suite.
@pytest.mark.slow
def test_kill_resume_bitwise_identical_trajectory(tmp_path):
    """SIGTERM after step 4 ⇒ atomic checkpoint + EXIT_PREEMPTED; the
    resumed run's steps 5..7 match an uninterrupted run's bit-for-bit.
    The uninterrupted run additionally absorbs an injected IOError
    (retried + accounted) — which must NOT perturb its trajectory, or
    the comparison below fails."""
    rc = _run(tmp_path, "solo", ["--num_steps", "7", "--chaos", "ioerror@6"])
    assert rc == 0
    log_solo = _log(tmp_path, "solo")
    assert "io-retry: retries=1 giveups=0" in log_solo

    # val_freq=4 makes step 4 BOTH a boundary save and the preemption
    # step: the preempted path must notice the step is already on disk
    # and not re-save (orbax raises StepAlreadyExists on a re-save,
    # which would turn the clean 75 exit into a crash).
    rc = _run(
        tmp_path, "killed",
        ["--num_steps", "7", "--val_freq", "4", "--chaos", "sigterm@4"],
    )
    assert rc == EXIT_PREEMPTED
    run_dir = tmp_path / "checkpoints" / "killed"
    assert (run_dir / "4").exists()  # the one atomic preemption save
    assert (run_dir / "resume_meta.json").exists()
    assert "preempted @ 4" in _log(tmp_path, "killed")
    # The clean solo run left NO flight dumps; the preempted run left
    # exactly ONE, for the drain trigger, naming the saved step
    # (observability/flight.py; docs/OBSERVABILITY.md trigger matrix).
    assert _flight_dumps(tmp_path, "solo") == []
    dumps = _flight_dumps(tmp_path, "killed")
    assert len(dumps) == 1 and dumps[0].startswith(
        "flight_preemption_drain_"
    )
    import json as _json

    dump = _json.load(open(run_dir / "flight" / dumps[0]))
    assert dump["context"] == {"step": 4, "checkpoint_step": 4}
    assert dump["report"]["health"]["train"]["state"] == "draining"

    rc = _run(
        tmp_path, "killed",
        ["--num_steps", "7", "--restore_ckpt", str(run_dir)],
    )
    assert rc == 0
    log_resumed = _log(tmp_path, "killed")
    assert "restored step 4" in log_resumed
    # The clean resume added no dump: still exactly one.
    assert _flight_dumps(tmp_path, "killed") == dumps

    solo, resumed = _trajectory(log_solo), _trajectory(log_resumed)
    assert set(range(1, 8)) <= set(solo)
    for step in (5, 6, 7):  # the post-resume steps
        assert resumed[step] == solo[step], (
            f"step {step} diverged after resume:\n"
            f"  uninterrupted: {solo[step]}\n"
            f"  resumed:       {resumed[step]}"
        )


def test_nan_chaos_under_strict_guards_skips_and_stays_sync_free(tmp_path):
    """A NaN batch mid-run: the sentinel skips it, counters reach
    log.txt, the run completes cleanly — and the strict guards prove the
    sentinel added no per-step host sync and no steady-state recompile."""
    rc = _run(
        tmp_path, "strict",
        ["--num_steps", "6", "--sum_freq", "2", "--strict_guards",
         "--chaos", "nan@2"],
    )
    assert rc == 0
    log = _log(tmp_path, "strict")
    assert "chaos: NaN flow injected into the batch for step 2" in log
    assert "sentinel @ 4: skipped=1" in log
    assert "steady_recompiles=0" in log
    assert "host_transfers=0" in log


def test_consecutive_bad_steps_halt_and_roll_back(tmp_path):
    """K consecutive bad steps ⇒ halt with EXIT_DIVERGED and rollback to
    the last good checkpoint. Steps 0-2 are good; the val_freq=2
    boundary saves at steps 2 and 4 (skip-updates keep the params
    last-good, so the step-4 save is still a good state); bad steps 3+
    trip the halt at consecutive=3."""
    nan = ",".join(f"nan@{s}" for s in range(3, 9))
    rc = _run(
        tmp_path, "diverge",
        ["--num_steps", "10", "--val_freq", "2",
         "--sentinel_halt_after", "3", "--chaos", nan],
    )
    assert rc == EXIT_DIVERGED
    log = _log(tmp_path, "diverge")
    assert "sentinel halt @ 6" in log
    assert "rolled back to last good checkpoint (step 4)" in log
    run_dir = tmp_path / "checkpoints" / "diverge"
    assert (run_dir / "4").exists()
    # The halt path must NOT have saved the post-halt state: no step
    # directory beyond the last boundary save.
    steps = sorted(int(d) for d in os.listdir(run_dir) if d.isdigit())
    assert steps[-1] == 4


def test_sentinel_halt_leaves_one_flight_dump_postmortem_reads(
    tmp_path, capsys
):
    """The rc-76 half of the flight-recorder acceptance: a sentinel-halt
    run leaves EXACTLY one valid dump (trigger sentinel_halt, health
    train=halted, the halt's step/consecutive context), and
    scripts/postmortem.py reassembles the fault's timeline from it —
    the train_sentinel_halt event is on the printed journey."""
    nan = ",".join(f"nan@{s}" for s in range(2, 8))
    rc = _run(
        tmp_path, "halted",
        ["--num_steps", "10", "--val_freq", "100",
         "--sentinel_halt_after", "3", "--chaos", nan],
    )
    assert rc == EXIT_DIVERGED
    dumps = _flight_dumps(tmp_path, "halted")
    assert len(dumps) == 1 and dumps[0].startswith(
        "flight_sentinel_halt_"
    )
    path = str(tmp_path / "checkpoints" / "halted" / "flight" / dumps[0])
    import json as _json

    dump = _json.load(open(path))
    assert dump["context"]["consecutive"] >= 3
    assert dump["report"]["health"]["train"]["state"] == "halted"
    capsys.readouterr()
    assert _postmortem([path]) == 0
    out = capsys.readouterr().out
    assert "trigger:      sentinel_halt" in out
    assert "train=halted" in out
    assert "train_sentinel_halt" in out  # the halt event on the journey


@pytest.mark.slow
def test_child_process_external_sigterm_kill_resume(tmp_path):
    """The satellite contract, with a real OS boundary: spawn a child
    train run, SIGTERM it from OUTSIDE mid-run, resume from its
    checkpoint, and the continued loss trajectory is bitwise-identical
    to an uninterrupted child run."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # No persistent compile cache on the CPU backend (utils/runtime.py):
    # this host's XLA CPU cache entries have produced glibc heap
    # corruption on reload (observed as SIGABRT in the resumed child).
    # Cold compiles are slower but deterministic.

    def spawn(name, extra):
        return subprocess.Popen(
            [sys.executable, os.path.join(REPO, "train.py")]
            + _args(tmp_path, name, extra),
            cwd=REPO,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )

    steps = 60
    proc = spawn("solo_child", ["--num_steps", str(steps)])
    out, err = proc.communicate(timeout=540)
    assert proc.returncode == 0, f"uninterrupted child failed:\n{out}\n{err}"
    solo = _trajectory(_log(tmp_path, "solo_child"))
    assert set(range(1, steps + 1)) <= set(solo)

    # Killed run: wait until the log shows real step progress (past
    # compile), then deliver a genuine external SIGTERM.
    proc = spawn("killed_child", ["--num_steps", str(steps)])
    log_path = tmp_path / "checkpoints" / "killed_child" / "log.txt"
    deadline = time.monotonic() + 480
    while time.monotonic() < deadline:
        if log_path.exists() and _trajectory(log_path.read_text()):
            break
        if proc.poll() is not None:
            break
        time.sleep(0.1)
    assert proc.poll() is None, "child finished before it could be killed"
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=540)
    assert proc.returncode == EXIT_PREEMPTED, (
        f"killed child rc={proc.returncode}\n{out}\n{err}"
    )
    log = _log(tmp_path, "killed_child")
    assert "preempted @" in log
    run_dir = tmp_path / "checkpoints" / "killed_child"
    saved = sorted(int(d) for d in os.listdir(run_dir) if d.isdigit())
    assert saved, "preemption saved no checkpoint"

    proc = spawn(
        "killed_child",
        ["--num_steps", str(steps), "--restore_ckpt", str(run_dir)],
    )
    out, err = proc.communicate(timeout=540)
    assert proc.returncode == 0, f"resumed child failed:\n{out}\n{err}"
    resumed = _trajectory(_log(tmp_path, "killed_child"))
    resume_from = saved[-1]
    post = [s for s in range(resume_from + 1, steps + 1)]
    assert post, "kill landed at the very end; nothing to compare"
    for step in post:
        assert resumed[step] == solo[step], (
            f"step {step} diverged after resume"
        )
