"""``eval_input_h2d_ms_per_pair``: the summed ``input_h2d`` span per pair dispatched:
the prefetch worker's host-to-device transfer of one staged batch.

The eval driver hands no report, so this reads the program's documented
operator surface itself, the process hub (``raft_ncup_tpu.observability.
get_telemetry()``). That hub is not reset between set-up and the window: the
value is a ratio of totals over every pass the process ran (warm-up, window,
check: one program, one shape), both totals counted at the same boundaries
(``input_h2d_ms`` sum over ``eval_pairs_total``). A program without the span or
the counter gives ``None``."""


def read(run: dict):
    from raft_ncup_tpu.observability import get_telemetry

    hub = get_telemetry()
    spans, pairs = hub.registry.get("input_h2d_ms"), hub.counter_value("eval_pairs_total")
    if spans is None or not pairs:
        return None
    return spans.sum_ms / pairs
