"""``train_dispatch_p50_ms``: median over the window of the program's
``train_dispatch`` span, the enqueue of the jitted training step alone (the
wait for the device has its own span, ``train_throttle_wait``); ``None``
where the program has no such span."""


def read(run: dict):
    return run["report"].get("stages", {}).get("train_dispatch", {}).get("p50_ms")
