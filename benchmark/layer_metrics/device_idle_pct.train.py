"""``device_idle_pct.train``: share of the traced training window in which
no operation ran on the device."""


def read(run: dict):
    if "trace" not in run or not run["window"].get("steps"):
        return None
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
