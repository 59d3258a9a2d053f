"""``corr_kernel_roofline_pct``: 100 x the least time the chip could take for
the traced window's correlation lookups (``benchmark/flops_corr.py``: the
larger of operations over peak FLOP/s and bytes over peak bytes/s, from
shapes alone, ``benchmark/peaks.json`` as published) over the device time the
lookup's Pallas kernels took (``corr_kernel_ms_per_pair``'s reading).

The shape and the iteration count are those of the window's own program: the
``metrics`` executable the eval driver lists under ``executable_memory``,
whose key holds ``'metrics', (batch, H, W, 3), (...), (...), iters, ...``
(``ShapeCachedForward.metrics``, after the backend and the mesh). ``None`` where the kernels are not listed,
no pair completed, the key is not there to read, or the device is not in the
peaks table."""

import os
import re

from benchmark import flops_corr, harness, meters

METRICS_KEY = re.compile(
    r"'metrics', \((\d+), (\d+), (\d+), \d+\), \([\d, ]*\), \([^()]*\), (\d+),"
)
HERE = os.path.dirname(os.path.abspath(__file__))


def read(run: dict):
    import jax

    kernels = harness.load_module(os.path.join(HERE, "corr_kernel_ms_per_pair.py"))
    seconds = kernels.kernel_seconds(run)
    pairs = run["window"].get("pairs")
    keys = [METRICS_KEY.search(str(e.get("key"))) for e in run["window"].get("executable_memory", [])]
    keys = [k for k in keys if k]
    if not seconds or not pairs or not keys:
        return None
    try:
        peaks = meters.load_peaks(jax.devices()[0].device_kind)
    except KeyError:
        return None
    _, h, w, iters = (int(x) for x in keys[0].groups())
    model = {}  # the geometry every configuration of the benchmark states: 4 levels, radius 4
    least = flops_corr.lookup_roofline_s(model, h // 8, w // 8, iters, peaks)["seconds"]
    return 100.0 * least * pairs / seconds
