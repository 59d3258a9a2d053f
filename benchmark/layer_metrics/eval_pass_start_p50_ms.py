"""``eval_pass_start_p50_ms``: nearest-rank median of the program's
``input_start`` span, from the construction of a pass's ``DevicePrefetcher``
to its first batch resident on the device and queued: what the host pays at
the head of every eval pass before the device has anything to do.

The eval driver hands no report, so this reads the program's documented
operator surface itself, the process hub (``raft_ncup_tpu.observability.
get_telemetry()``), as ``eval_input_wait_ms_per_pair`` does. That hub is not
reset between set-up and the window, so the sample holds every pass the
process ran (warm-up, window, check); a median over them is a window pass.
``None`` where the program has no such span."""


def read(run: dict):
    from raft_ncup_tpu.observability import get_telemetry

    spans = get_telemetry().registry.get("input_start_ms")
    if spans is None or not spans.count:
        return None
    return spans.percentile_ms(0.50)
