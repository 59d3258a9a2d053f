"""Jitted (and optionally mesh-sharded) train/eval steps.

One step function serves single-chip and multi-chip runs: with a mesh, the
batch is sharded over (data, spatial) and parameters are replicated; XLA's
SPMD partitioner inserts the gradient psums and conv halo exchanges. This
replaces the reference's DataParallel scatter/gather (train.py:169-215)
with compiler-inserted collectives over ICI.

BatchNorm under data parallelism computes statistics over the *global*
batch (sync-BN): the batch reduction crosses the sharded axis, so XLA
emits the cross-replica reduction — strictly better-behaved than the
reference's DataParallel per-replica stats.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from raft_ncup_tpu.config import TrainConfig
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.parallel.mesh import batch_sharding, replicated
from raft_ncup_tpu.resilience.anomaly import guard_update
from raft_ncup_tpu.training.loss import sequence_loss
from raft_ncup_tpu.training.state import TrainState


# Step-function reuse across trainer invocations in one process: two
# models with equal ModelConfig compute identically (flax modules carry
# only their config), so the jitted step — and, with the shared
# optimizer transform from training/optim.py, its compiled executable —
# can be reused instead of re-traced. This is what makes an in-process
# kill/resume cycle (resilience tests, notebook restarts) pay restore
# latency rather than a full recompile. Keyed on every config field the
# traced step reads; bounded FIFO so a config-sweeping process cannot
# pin unboundedly many executables (callers keep their own references —
# eviction only means a later identical request re-traces).
_STEP_CACHE: dict = {}
_STEP_CACHE_MAX = 8


def _step_cache_key(model_cfg, cfg: TrainConfig, mesh) -> tuple:
    return (
        model_cfg, mesh,
        cfg.stage != "chairs",  # freeze_bn (reference: train.py:185-186)
        cfg.add_noise, cfg.iters, cfg.gamma, cfg.max_flow,
        cfg.anomaly_sentinel, cfg.sentinel_spike_factor,
        cfg.sentinel_ema_decay, cfg.sentinel_warmup,
    )


def make_train_step(
    model: RAFT,
    cfg: TrainConfig,
    mesh: Optional[Mesh] = None,
):
    """Returns ``step(state, batch, rng) -> (state, metrics)``.

    ``batch``: dict with image1/image2 (B, H, W, 3) uint8 or float32 in
    [0, 255] (the loader ships uint8; the cast happens on device), flow
    (B, H, W, 2), valid (B, H, W).

    ``step.report["bn_layers_training"]``, once the step has been traced
    (its first call or ``lower``): the BatchNorm layers whose running
    statistics a call replaces; 0 where ``freeze_bn`` holds.
    """
    cache_key = _step_cache_key(model.cfg, cfg, mesh)
    cached = _STEP_CACHE.get(cache_key)
    if cached is not None:
        return cached
    freeze_bn = cfg.stage != "chairs"  # reference: train.py:185-186
    report: dict = {}  # filled while the step is traced; ``jitted.report``

    def loss_fn(params, batch_stats, batch, rng):
        img1 = batch["image1"].astype(jnp.float32)
        img2 = batch["image2"].astype(jnp.float32)
        if cfg.add_noise:
            # Gaussian noise with per-step uniform stddev in [0, 5]
            # (reference: train.py:210-213).
            kstd, k1, k2 = jax.random.split(rng, 3)
            stdv = jax.random.uniform(kstd, (), maxval=5.0)
            img1 = jnp.clip(
                img1 + stdv * jax.random.normal(k1, img1.shape), 0.0, 255.0
            )
            img2 = jnp.clip(
                img2 + stdv * jax.random.normal(k2, img2.shape), 0.0, 255.0
            )

        variables = {"params": params, "batch_stats": batch_stats}
        preds, new_stats = model.apply(
            variables,
            img1,
            img2,
            iters=cfg.iters,
            train=True,
            freeze_bn=freeze_bn,
            rngs={"dropout": rng} if model.cfg.dropout > 0 else None,
            mutable=True,
            mesh=mesh,
        )
        # What this trace of the step does with the running statistics, for
        # the loop's counter and gauge (docs/OBSERVABILITY.md): the layers
        # of every submodule whose statistics the forward replaced.
        report["bn_layers_training"] = sum(
            bn_layer_count(new_stats[name]) for name in new_stats
            if new_stats[name] is not batch_stats.get(name)
        )
        loss, metrics = sequence_loss(
            preds, batch["flow"], batch["valid"], cfg.gamma, cfg.max_flow
        )
        return loss, (metrics, new_stats)

    def step(state: TrainState, batch: dict, rng: jax.Array):
        # jax.named_scope: stage labels in the compiled step's HLO, by
        # which a capture's reduction splits forward + backward /
        # optimizer / sentinel device time (docs/OBSERVABILITY.md;
        # utils/profiling.scope_of).
        with jax.named_scope("train.forward_backward"):
            (loss, (metrics, new_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params, state.batch_stats, batch, rng)
        with jax.named_scope("train.optimizer_update"):
            new_state = state.apply_gradients(
                grads, new_batch_stats=new_stats
            )
            metrics = dict(metrics)
            metrics["loss"] = loss
            metrics["grad_norm"] = optax.global_norm(grads)
        if cfg.anomaly_sentinel:  # static flag: one fixed compiled program
            # Divergence sentinel (resilience/anomaly.py): a non-finite or
            # grad-spiking step selects the OLD params/opt_state via
            # jnp.where — fully on device, no host sync, no extra program.
            with jax.named_scope("train.sentinel"):
                new_state, sen_metrics = guard_update(
                    state, new_state, loss, metrics["grad_norm"], cfg
                )
            metrics.update(sen_metrics)
        return new_state, metrics

    if mesh is None:
        jitted = jax.jit(step, donate_argnums=0)
    else:
        repl = replicated(mesh)
        jitted = jax.jit(
            step,
            in_shardings=(repl, batch_sharding(mesh), repl),
            out_shardings=(repl, repl),
            donate_argnums=0,
        )
    jitted.report = report
    while len(_STEP_CACHE) >= _STEP_CACHE_MAX:
        _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
    _STEP_CACHE[cache_key] = jitted
    return jitted


def bn_layer_count(batch_stats) -> int:
    """BatchNorm layers in a ``batch_stats`` tree: one running mean each."""
    return sum(
        1 for path, _ in jax.tree_util.tree_leaves_with_path(batch_stats)
        if getattr(path[-1], "key", None) == "mean"
    )


def make_synthetic_batch(rng: jax.Array, batch: int, height: int, width: int):
    """Random (image1, image2, flow, valid) batch in the train-step's
    contract — shared by the bench's train-step measurement and the
    driver's multichip dryrun so both exercise the same workload."""
    k1, k2, k3 = jax.random.split(rng, 3)
    B, H, W = batch, height, width
    return {
        "image1": jax.random.uniform(k1, (B, H, W, 3), jnp.float32, 0, 255),
        "image2": jax.random.uniform(k2, (B, H, W, 3), jnp.float32, 0, 255),
        "flow": jax.random.normal(k3, (B, H, W, 2), jnp.float32),
        "valid": jnp.ones((B, H, W), jnp.float32),
    }


def make_eval_step(model: RAFT, iters: int, mesh: Optional[Mesh] = None):
    """Returns ``eval_step(variables, image1, image2) -> (flow_lr, flow_up)``
    (test-mode forward)."""

    def step(variables, image1, image2):
        return model.apply(
            variables, image1, image2, iters=iters, test_mode=True, mesh=mesh
        )

    if mesh is None:
        return jax.jit(step)
    repl = replicated(mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P

    img = NamedSharding(mesh, P("data", "spatial", None, None))
    return jax.jit(
        step, in_shardings=(repl, img, img), out_shardings=(repl, repl)
    )
