"""``stream_device_wait_p50_ms``: median of the program's ``stream_device_wait`` span over the
window (bucketed histogram of the span tracer, ``StreamEngine.report()``)."""


def read(run: dict):
    return run["report"].get("stages", {}).get("stream_device_wait", {}).get("p50_ms")
