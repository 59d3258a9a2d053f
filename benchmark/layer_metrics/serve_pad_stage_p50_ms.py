"""``serve_pad_stage_p50_ms``: median over the window of the program's ``serve_pad_stage``
span, the host pad of each request to the padded shape, the zero rows up to the batch
size, and the stack (span tracer histogram, ``FlowServer.report()``); ``None`` where the
program has no such span."""


def read(run: dict):
    return run["report"].get("stages", {}).get("serve_pad_stage", {}).get("p50_ms")
