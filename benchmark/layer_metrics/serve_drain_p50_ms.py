"""``serve_drain_p50_ms``: median of the program's ``serve_drain`` span over the
window (bucketed histogram of the span tracer, ``FlowServer.report()``)."""


def read(run: dict):
    return run["report"].get("stages", {}).get("serve_drain", {}).get("p50_ms")
