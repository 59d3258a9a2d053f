"""Training metrics logging: text file + console + TensorBoard.

Covers the reference ``Logger`` (reference: train.py:102-164): running
means printed every ``sum_freq`` steps, args dumped once at startup,
train scalars and validation dicts to TensorBoard — without the
reference's reliance on a global ``args`` and its lazily-created default
writer (quirks noted in SURVEY.md §5).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Mapping, Optional

import jax


class Logger:
    def __init__(
        self,
        run_dir: str,
        config: Any = None,
        sum_freq: int = 100,
        use_tensorboard: bool = True,
        active: bool = True,
    ):
        """``active=False`` makes every output a no-op — the non-main
        processes of a pod, which would otherwise interleave N copies of
        log.txt/TensorBoard into the same shared run_dir (the reference
        is single-process and never faces this — train.py:102-164)."""
        self.run_dir = run_dir
        self.sum_freq = sum_freq
        self.active = active
        self._txt = None
        self._writer = None
        # Metrics accumulate as running sums ON DEVICE (device scalars stay
        # device scalars; `+` dispatches asynchronously) and are pulled to
        # host with ONE jax.device_get only when a summary fires. A per-push
        # float(v) would be a per-step block_until_ready — it collapses
        # JAX's async dispatch and puts a host round-trip on the critical
        # path of every training step.
        self._acc: dict[str, Any] = {}
        self._acc_n = 0
        if not active:
            return
        os.makedirs(run_dir, exist_ok=True)
        self._txt = open(os.path.join(run_dir, "log.txt"), "a")
        if use_tensorboard:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._writer = SummaryWriter(
                    log_dir=os.path.join(run_dir, "tb")
                )
            except ImportError:
                pass
        self._t_last = time.perf_counter()
        self._steps_last: Optional[int] = None
        if config is not None:
            self.write_text(self._config_str(config))

    @staticmethod
    def _config_str(config: Any) -> str:
        try:
            from raft_ncup_tpu.config import config_to_json

            return config_to_json(config)
        except Exception:
            return repr(config)

    def write_text(self, text: str) -> None:
        if not self.active:
            return
        self._txt.write(text + "\n")
        self._txt.flush()

    def push(self, step: int, metrics: Mapping[str, Any], lr: Optional[float] = None) -> None:
        """Accumulate one step's metrics; emit a summary every sum_freq
        steps (reference: train.py:124-139).

        Between summaries this performs ZERO host transfers: device
        scalars are summed on device (async dispatch), and the single
        ``jax.device_get`` at the boundary is the only synchronization
        point the logger ever introduces."""
        if not self.active:
            return
        for k, v in metrics.items():
            prev = self._acc.get(k)
            self._acc[k] = v if prev is None else prev + v
        self._acc_n += 1
        if self._steps_last is None:
            self._steps_last = step  # first push after start/resume
        if (step + 1) % self.sum_freq == 0 and self._acc_n:
            # ONE transfer for the whole window, lr riding along as its
            # own tree leaf (a dict key would collide with a metric of the
            # same name): float(lr) on a schedule that returns a device
            # scalar would be an implicit pull (JGL001's runtime analogue
            # — guards.py flags it under --strict_guards).
            # The pull waits for whatever step is still in flight: device
            # time seen from the host, under ``train_metrics_pull``.
            from raft_ncup_tpu.observability import get_telemetry

            tel = get_telemetry()
            with tel.span("train_metrics_pull", step=step):
                sums, lr = jax.device_get((self._acc, lr))
            lr = None if lr is None else float(lr)
            means = {k: float(v) / self._acc_n for k, v in sums.items()}
            self._acc, self._acc_n = {}, 0
            now = time.perf_counter()
            sps = (step + 1 - self._steps_last) / max(now - self._t_last, 1e-9)
            # Telemetry mirror (observability/): the window means ride
            # the SAME boundary pull as host floats into gauges — the
            # training loop's scalars join the one registry every other
            # subsystem reports to, at zero additional syncs.
            for k, v in means.items():
                tel.gauge_set(f"train_{k}", v)
            tel.gauge_set("train_steps_per_sec", sps)
            if lr is not None:
                tel.gauge_set("train_lr", lr)
            self._t_last, self._steps_last = now, step + 1
            parts = [f"[{step + 1:6d}"]
            if lr is not None:
                parts.append(f"lr {lr:.2e}")
            parts.append(f"{sps:5.2f} it/s]")
            parts += [f"{k} {v:.4f}" for k, v in sorted(means.items())]
            line = " ".join(parts)
            print(line, flush=True)
            self.write_text(line)
            if self._writer is not None:
                for k, v in means.items():
                    self._writer.add_scalar(f"train/{k}", v, step + 1)
                if lr is not None:
                    self._writer.add_scalar("train/lr", lr, step + 1)
                self._writer.add_scalar("train/steps_per_sec", sps, step + 1)

    def write_dict(self, step: int, results: Mapping[str, float]) -> None:
        """Log a validation-results dict (reference: train.py:151-161)."""
        if not self.active:
            return
        line = f"[val @ {step}] " + json.dumps(
            {k: round(float(v), 5) for k, v in results.items()}
        )
        print(line, flush=True)
        self.write_text(line)
        if self._writer is not None:
            for k, v in results.items():
                self._writer.add_scalar(f"val/{k}", float(v), step)

    def close(self) -> None:
        if self._txt is not None:
            self._txt.close()
        if self._writer is not None:
            self._writer.close()
