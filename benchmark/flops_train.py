"""Operations one training step needs, from shapes alone, beside
``flops.py`` (which is not edited): the forward with the upsampler in every
iteration, twice that for the backward (one product for the input's
cotangent, one for the weight's, per forward product).

``model`` counts what the algorithm requires, forward x 3: the figure a
model-FLOP/s utilisation is made from, in which recomputed operations do
not count. ``executed`` adds what the program's rematerialisation runs a
second time: the refinement loop (``jax.checkpoint`` of the scan body: update
block and upsampler in every iteration) and both encoders (PR 26).
"""

from __future__ import annotations

from benchmark import flops


def train_step_flops(model: dict, batch: int, height: int, width: int, iters: int) -> dict:
    forward = flops.forward_flops(
        model, batch, height, width, iters, upsample_every_iteration=True
    )
    volume = batch * 2.0 * ((height // 8) * (width // 8)) ** 2 * 256
    recomputed = forward - volume  # encoders and loop; the volume is kept
    return {
        "analytic_forward_flops_per_step": forward,
        "analytic_model_flops_per_step": 3.0 * forward,
        "analytic_executed_flops_per_step": 3.0 * forward + recomputed,
    }
