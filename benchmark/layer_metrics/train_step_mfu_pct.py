"""``train_step_mfu_pct``: the whole training step's share of the chip's
peak, from the device trace: the operations the algorithm needs for a step
(``benchmark/flops_train.py``: three forwards, recomputed operations not
counted) x the traced window's steps / the seconds the device was busy in it
(the union of the operation intervals on the device plane, as
``train_device_ms_per_step`` has it) / the chip's bfloat16 peak
(``benchmark/peaks.json``; the float32 pins are counted against it too: it
is the chip's peak, not the policy's). The host's part of a step (dispatch,
input wait) is not in it: that is ``device_idle_pct.train``. ``None`` in an
untraced run, off the TPU (no peak) and where the window has no operation
count."""


def read(run: dict):
    window = run["window"]
    share = window.get("analytic_model_flops_utilisation_pct")  # of the window's seconds
    if share is None or "trace" not in run or not run["trace"].get("busy_s"):
        return None
    return share * window["window_s"] / run["trace"]["busy_s"]
