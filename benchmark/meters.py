"""Small yardsticks the benchmark owns: the compile meter, the nearest-rank
percentile, the table of peaks and the device record.

``CompileMeter`` and ``nearest_rank`` are copies of the program's
``chip_smoke.CompileMeter`` and ``serving/request.nearest_rank_ms`` (see
PERF.md, Open questions): the benchmark may not read its arithmetic from
the code it measures.
"""

from __future__ import annotations

import json
import math
import os
from typing import Optional, Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))


class CompileMeter:
    """XLA compiles, their wall seconds and persistent-cache hits/misses,
    from jax.monitoring. A program loaded from the persistent cache also
    fires the duration event (with the seconds the load took), so "no
    event inside the window" means nothing was compiled OR loaded there."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self._COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "compiles": self.compiles, "compile_s": self.compile_s,
            "cache_hits": self.hits, "cache_misses": self.misses,
        }


def nearest_rank(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile: the value at index ceil(p*n) - 1 of the
    sorted sample, unrounded. ``None`` on an empty sample."""
    if not values:
        return None
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(p * len(xs)) - 1))]


def load_peaks(device_kind: str) -> dict:
    """The peaks of one chip, keyed by ``device_kind``. A chip that is not
    in the table is an error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json; "
            "add it with its source, do not guess"
        )
    return table[device_kind]


def device_record(devices) -> dict:
    """Platform, kind and count as jax reports them, and the peak bytes on
    the fullest device. On the TPU runtime ``peak_bytes_in_use`` counts live
    buffers only; the scratch XLA reserves for loaded programs (their
    ``temp_size``) is under ``peak_bytes_reserved`` and not part of it (my
    chip runs, PR 23: 0.73 GB in use beside 5.59 GB reserved for a program of
    5.63 GB temp), so the peak is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(
            int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0))
        )
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }
