"""``infer_mfu_pct``: the test-mode forward's share of the chip's peak, from
the device trace: the operations the algorithm needs for the traced window's
pairs (``benchmark/flops.py``: encoders, volume, every refinement iteration,
NCUP ONCE, after the loop, as the test-mode ``raft_nc_dbl`` program runs it:
``forward_flops`` counts the upsampler ``iters`` times only for a training
forward) / the seconds the DEVICE was busy in that window (the union of the
operation intervals on the device plane, as ``device_ms_per_pair`` has it) /
the chip's bfloat16 peak (``benchmark/peaks.json``; the float32 pins are
counted against it too: it is the chip's peak, not the policy's). Read as
``train_step_mfu_pct`` is: the window's ``analytic_flops_utilisation_pct``
is of the window's seconds on the host's clock, so it is scaled to the busy
seconds and the host's share of a pass (its start, the staging) is not in it:
that is ``device_idle_pct.infer``. ``None`` in an untraced run, off the TPU
(no peak) and where the window has no operation count."""


def read(run: dict):
    window = run["window"]
    share = window.get("analytic_flops_utilisation_pct")  # of the window's seconds
    if share is None or "trace" not in run or not run["trace"].get("busy_s"):
        return None
    return share * window["window_s"] / run["trace"]["busy_s"]
