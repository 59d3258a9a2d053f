"""``device_ms_per_pair``: device busy time of the traced window (union
of the operation intervals on the device plane) per pair completed in it."""


def read(run: dict):
    pairs = run["window"].get("pairs")
    if "trace" not in run or not pairs:
        return None
    return 1000.0 * run["trace"]["busy_s"] / pairs
