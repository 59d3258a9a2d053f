"""``serve_throttle_wait_p50_ms``: median over the window of the program's
``serve_throttle_wait`` span, the dispatcher's bounded wait for an earlier batch to
finish on the device (``DispatchThrottle.push``) (span tracer histogram,
``FlowServer.report()``); ``None`` where the program has no such span."""


def read(run: dict):
    return run["report"].get("stages", {}).get("serve_throttle_wait", {}).get("p50_ms")
