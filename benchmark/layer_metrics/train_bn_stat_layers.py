"""``train_bn_stat_layers``: BatchNorm layers whose running statistics a step
of the window replaced: the counter ``train_bn_stat_updates_total`` (layers
the dispatched step's own trace says it trains, summed over the window's
steps) over ``train_steps_total``, both of the window alone (the driver
``train_steps_bn`` puts them in the window's report). 15 in the chairs stage
of the ``raft`` model (the context encoder's stem, two a residual block, one
a strided shortcut), 0 in a step that freezes BatchNorm. The ledger's
direction means nothing for it: ``correct`` holds the count to the
reference's (``bn_stat_updates_gap`` = 0). ``None`` where the program has no
such counter or the window no step."""


def read(run: dict):
    report = run["report"]
    updates, steps = report.get("train_bn_stat_updates_total"), report.get("train_steps_total")
    if updates is None or not steps:
        return None
    return updates / steps
