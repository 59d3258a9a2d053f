"""``serve_dispatch_p50_ms``: median over the window of the program's ``serve_dispatch``
span, the host-to-device copy of the staged batch and the jit dispatch alone (the
throttle's wait has its own span since PR 24; a program older than that still counts it
here) (span tracer histogram, ``FlowServer.report()``); ``None`` where the program has
no such span."""


def read(run: dict):
    return run["report"].get("stages", {}).get("serve_dispatch", {}).get("p50_ms")
