"""``stream_throttle_wait_p50_ms``: median of the program's ``stream_throttle_wait`` span over the
window (bucketed histogram of the span tracer, ``StreamEngine.report()``)."""


def read(run: dict):
    return run["report"].get("stages", {}).get("stream_throttle_wait", {}).get("p50_ms")
