"""``corr_kernel_ms_per_pair``: device time of the traced window spent in the
correlation lookup's Pallas kernels, per pair completed in it.

A Pallas call is one ``custom-call`` on the device's ``XLA Ops`` line, named
after the call's ``name``: ``corr_banded_l<level>`` / ``corr_resident_l<level>``
(``ops/corr_pallas.py``; ``corr_lookup_banded`` / ``corr_lookup_resident``
before the levels had names of their own). ``run["trace"]["device_ops"]`` is
the reduced trace's list of the operations with the most summed time, so a
kernel that has fallen off that list is not counted: the reading is a floor
of the kernels' time. ``None`` where no such operation is listed (another
lookup path, or a program without the kernels)."""

import re

KERNEL = re.compile(r"corr_(?:lookup_)?(?:banded|resident)")


def kernel_seconds(run: dict):
    """Summed device seconds of the listed kernel operations, or ``None``."""
    listed = [
        seconds for name, seconds in run.get("trace", {}).get("device_ops", [])
        if KERNEL.search(name)
    ]
    return sum(listed) if listed else None


def read(run: dict):
    pairs = run["window"].get("pairs")
    seconds = kernel_seconds(run)
    if seconds is None or not pairs:
        return None
    return 1000.0 * seconds / pairs
