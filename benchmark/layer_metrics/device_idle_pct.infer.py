"""``device_idle_pct.infer``: share of the traced window in which no
operation ran on the device."""


def read(run: dict):
    if "trace" not in run:
        return None
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
