"""``serve_device_wait_p50_ms``: median over the window of the program's
``serve_device_wait`` span, the drain worker's wait for the batch's program to finish
(``block_until_ready`` on the drain tree) (span tracer histogram,
``FlowServer.report()``); ``None`` where the program has no such span."""


def read(run: dict):
    return run["report"].get("stages", {}).get("serve_device_wait", {}).get("p50_ms")
