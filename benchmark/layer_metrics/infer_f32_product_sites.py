"""``infer_f32_product_sites``: how many product sites of the timed test-mode
forward took float32 operands, from the program's trace-time tally
(``raft_ncup_tpu/precision/sites.py``, banked with the executable in the cost
ledger and handed out by ``ShapeCachedForward.report()``; the driver
``eval_pass_mixed`` puts it in the window's report): the pinned float32
islands counted. The ledger's direction means nothing for it: ``correct``
holds the sites to the configuration's ``pinned_sites``, name for name
(``f32_product_sites_gap`` = 0), so a run that reports another count is not
correct, whichever way it moved. ``None`` where the pass reports no tally."""


def read(run: dict):
    return (run["report"].get("precision") or {}).get("sites_f32")
