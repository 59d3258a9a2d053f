"""``stream_dispatch_p50_ms``: median of the program's ``stream_dispatch`` span over the
window (bucketed histogram of the span tracer, ``StreamEngine.report()``)."""


def read(run: dict):
    return run["report"].get("stages", {}).get("stream_dispatch", {}).get("p50_ms")
