"""``eval_fill_rows_pct``: 100 x fill rows / rows dispatched in the window,
from the deltas of the program's counters ``eval_fill_rows_total`` and
``eval_rows_total`` (``evaluation._run_metric_pass`` grows both where
``eval_dispatch`` closes; the driver ``eval_pass_kitti`` puts the window's
deltas in its report). A fill row is a row a masked validation pass adds to
make a size's remainder a whole batch: device time that completes no pair,
the price of one program a native size. 16 of 216 rows a pass at batch 8
(7.4). ``None`` where the program has no such counters or the driver hands
no report."""


def read(run: dict):
    c = run["report"].get("counters", {})
    rows = c.get("eval_rows_total")
    if not rows or "eval_fill_rows_total" not in c:
        return None
    return 100.0 * c["eval_fill_rows_total"] / rows
