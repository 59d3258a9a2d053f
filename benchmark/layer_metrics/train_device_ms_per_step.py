"""``train_device_ms_per_step``: device busy time of the traced window (union
of the operation intervals on the device plane) per training step dispatched
and finished in it."""


def read(run: dict):
    steps = run["window"].get("steps")
    if "trace" not in run or not steps:
        return None
    return 1000.0 * run["trace"]["busy_s"] / steps
