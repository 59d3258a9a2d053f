"""Train state: params + batch_stats + optimizer state + step counter.

Unlike the reference — which checkpoints only model weights and silently
restarts the LR schedule on resume (SURVEY.md §5 checkpoint/resume) — the
full state (including optimizer moments and step) is a single pytree,
checkpointed with orbax in ``raft_ncup_tpu.training.checkpoint``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import optax
from flax import struct

from raft_ncup_tpu.config import ModelConfig, TrainConfig
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.training.optim import build_optimizer, freeze_raft_mask


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    # Divergence-sentinel accumulators (resilience/anomaly.py), carried in
    # the state pytree so they live on device and ride the same donated
    # buffers as the optimizer state. None when the sentinel is disabled
    # (an empty pytree subtree — invisible to tree ops and shardings).
    sentinel: Any = None

    def apply_gradients(self, grads, new_batch_stats=None):
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=(
                new_batch_stats if new_batch_stats is not None else self.batch_stats
            ),
        )


def create_train_state(
    rng: jax.Array,
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    image_shape: Optional[tuple[int, ...]] = None,
    variables: Optional[dict] = None,
) -> tuple[RAFT, TrainState]:
    """Build the model, initialize variables (or start from ``variables``,
    a tree in the checkpoint layout), and assemble the optimizer (with the
    freeze_raft mask when configured)."""
    import jax.numpy as jnp

    model = RAFT(model_cfg)
    if variables is None:
        if image_shape is None:
            h, w = train_cfg.image_size
            image_shape = (1, h, w, 3)
        variables = model.init(rng, image_shape)
    else:
        # The step donates its state: train on copies, so the caller's
        # tree survives the first step.
        variables = jax.tree.map(jnp.array, variables)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})

    mask = freeze_raft_mask(params) if model_cfg.freeze_raft else None
    tx = build_optimizer(train_cfg, trainable_mask=mask)
    opt_state = tx.init(params)

    sentinel = None
    if getattr(train_cfg, "anomaly_sentinel", False):
        from raft_ncup_tpu.resilience.anomaly import init_sentinel

        sentinel = init_sentinel()

    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
        tx=tx,
        sentinel=sentinel,
    )
    return model, state
