"""``serve_pull_p50_ms``: median over the window of the program's ``serve_pull`` span, the
device-to-host copy of the batch's flow alone (``device_get`` after the wait) (span
tracer histogram, ``FlowServer.report()``); ``None`` where the program has no such span."""


def read(run: dict):
    return run["report"].get("stages", {}).get("serve_pull", {}).get("p50_ms")
