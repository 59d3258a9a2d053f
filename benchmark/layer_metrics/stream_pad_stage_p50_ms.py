"""``stream_pad_stage_p50_ms``: median of the program's ``stream_pad_stage`` span over the
window (bucketed histogram of the span tracer, ``StreamEngine.report()``)."""


def read(run: dict):
    return run["report"].get("stages", {}).get("stream_pad_stage", {}).get("p50_ms")
