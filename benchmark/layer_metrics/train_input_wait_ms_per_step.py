"""``train_input_wait_ms_per_step``: the summed ``input_wait`` span (the
training loop's wait inside ``next(prefetcher)`` for a staged,
device-resident batch, the one input stage on the critical path) over the
counter ``train_steps_total``, both of the window alone (the training
driver resets the process hub where the window opens and hands both in its
report). A program without the span or the counter gives ``None``."""


def read(run: dict):
    report = run["report"]
    total, steps = report.get("input_wait_ms_sum"), report.get("train_steps_total")
    if total is None or not steps:
        return None
    return total / steps
