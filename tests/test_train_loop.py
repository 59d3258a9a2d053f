"""The training loop the package owns (``training/loop.py``), its spans and
counters, and the rematerialisation of the training forward (PR 26)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.config import DataConfig, ModelConfig, TrainConfig, flagship_config
from raft_ncup_tpu.data import ArrayFlowDataset, SyntheticFlowDataset
from raft_ncup_tpu.data.synthetic import make_pair
from raft_ncup_tpu.models import raft as raft_module
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.nn import weights_est
from raft_ncup_tpu.observability import Telemetry, set_telemetry, telemetry_report
from raft_ncup_tpu.training.logger import Logger
from raft_ncup_tpu.training.loop import open_train_run, train_steps
from raft_ncup_tpu.training.loss import sequence_loss
from raft_ncup_tpu.utils import remat

HW = (64, 96)


def _batch(seed=0, b=2):
    rng = np.random.default_rng(seed)
    pairs = [make_pair(rng, HW, 6.0) for _ in range(b)]
    out = {k: jnp.asarray(np.stack([p[k] for p in pairs])) for k in ("image1", "image2", "flow")}
    out["valid"] = jnp.ones((b,) + HW, jnp.float32)
    return out


@pytest.fixture()
def hub():
    tel = Telemetry()
    prev = set_telemetry(tel)
    yield tel
    set_telemetry(prev)


def _run(tmp_path, **kw):
    train_cfg = TrainConfig(
        stage="sintel", batch_size=2, image_size=HW, iters=2, num_steps=10,
        sum_freq=2, checkpoint_dir=str(tmp_path),
    )
    data_cfg = DataConfig(num_workers=1)
    dataset = SyntheticFlowDataset(HW, length=8)
    return open_train_run(
        flagship_config(dataset="sintel"), train_cfg, data_cfg, dataset=dataset, **kw
    )


def test_loop_counts_steps_spans_and_stops_where_told(tmp_path, hub):
    run = _run(tmp_path)
    logger = Logger(str(tmp_path / "log"), sum_freq=2, use_tensorboard=False)
    seen = []
    try:
        train_steps(run, lambda i: i >= 3, logger=logger,
                    after_step=lambda i, m: seen.append((i, m["loss"])) or False)
        assert run.step_i == 3 and int(run.state.step) == 3
        assert [i for i, _ in seen] == [1, 2, 3]
        assert all(np.isfinite(float(v)) for _, v in seen)
        # after_step returning True ends the loop after that step
        train_steps(run, lambda i: False, after_step=lambda i, m: i >= 5)
        assert run.step_i == 5
    finally:
        run.close()
        logger.close()
    assert hub.counter_value("train_steps_total") == 5
    assert hub.counter_value("train_pairs_total") == 10
    # every stage but chairs freezes BatchNorm: no layer's statistics are replaced
    assert hub.counter_value("train_bn_stat_updates_total") == 0
    assert hub.registry.get("train_bn_layers_training").value == 0
    stages = telemetry_report(hub)["stages"]
    for name, count in (("train_dispatch", 5), ("train_throttle_wait", 5),
                        ("train_metrics_pull", 1), ("input_wait", 5)):
        assert stages[name]["count"] == count, name
    assert not run.throttle._pending  # every dispatched step has finished


def test_the_chairs_stage_publishes_the_layers_its_step_trains(tmp_path, hub):
    """The chairs stage trains BatchNorm (PR 49): the loop's counter grows by
    the layers whose running statistics the dispatched step replaced (the
    flagship's: cnet's 15 and the weights net's 2, carried through the scan),
    the gauge holds them, and the state's statistics move every step."""
    train_cfg = TrainConfig(stage="chairs", batch_size=2, image_size=HW, iters=2,
                            num_steps=10, checkpoint_dir=str(tmp_path))
    run = open_train_run(flagship_config(dataset="sintel"), train_cfg, DataConfig(num_workers=1),
                         dataset=SyntheticFlowDataset(HW, length=8))
    stats = [jax.tree.map(np.asarray, run.state.batch_stats)]
    try:
        for n in (1, 2):
            train_steps(run, lambda i: i >= n)
            stats.append(jax.tree.map(np.asarray, run.state.batch_stats))
    finally:
        run.close()
    assert run.step_fn.report == {"bn_layers_training": 17}
    assert hub.counter_value("train_bn_stat_updates_total") == 2 * 17
    assert hub.registry.get("train_bn_layers_training").value == 17
    for before, after in zip(stats, stats[1:]):
        moved = jax.tree.map(lambda a, b: bool(np.any(a != b)), before, after)
        assert set(moved) == {"cnet", "upsampler"} and all(jax.tree.leaves(moved))


def test_run_starts_from_given_weights_and_keeps_the_callers_tree(tmp_path, hub):
    model = RAFT(flagship_config(dataset="sintel"))
    variables = model.init(jax.random.PRNGKey(7), (1,) + HW + (3,))
    before = jax.tree.map(np.asarray, variables)
    run = _run(tmp_path, variables=variables)
    try:
        got = jax.tree.map(np.asarray, run.state.params)
        assert all(np.array_equal(a, b) for a, b in
                   zip(jax.tree.leaves(got), jax.tree.leaves(before["params"])))
        train_steps(run, lambda i: i >= 1)
    finally:
        run.close()
    # the step donates its state; the caller's arrays are still readable
    after = jax.tree.map(np.asarray, variables)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(after), jax.tree.leaves(before)))


def test_array_dataset_goes_through_the_file_datasets_sample():
    rng = np.random.default_rng(0)
    pairs = [make_pair(rng, (92, 128), 6.0) for _ in range(3)]
    ds = ArrayFlowDataset(pairs, dict(crop_size=HW, min_scale=-0.2, max_scale=0.6, do_flip=True))
    s = ds.sample(4, np.random.default_rng(1))  # index wraps
    assert len(ds) == 3 and s["image1"].shape == HW + (3,) and s["image1"].dtype == np.uint8
    assert s["flow"].shape == HW + (2,) and s["valid"].shape == HW and s["valid"].dtype == np.float32
    plain = ArrayFlowDataset(pairs).sample(1)
    assert np.array_equal(plain["image2"], pairs[1]["image2"])


# --------------------------------------------------------- rematerialisation


def _model(variant, precision):
    return RAFT(ModelConfig(variant=variant, dataset="sintel", precision=precision))


def _loss_fn(model, variables, batch, remat):
    def loss_fn(params):
        preds = model.apply(
            {**variables, "params": params}, batch["image1"].astype(jnp.float32),
            batch["image2"].astype(jnp.float32), iters=3, train=True, freeze_bn=True,
            remat=remat,
        )
        return sequence_loss(preds, batch["flow"], batch["valid"], 0.85)[0]

    return jax.jit(jax.value_and_grad(loss_fn))


@pytest.mark.parametrize("precision", ["f32", "bf16_train"])
@pytest.mark.parametrize("variant", ["raft_nc_dbl", "raft"])
def test_rematerialised_step_is_the_old_arithmetic(variant, precision, monkeypatch):
    """Encoders and loop body rematerialised, the loop's named values kept
    (what the step compiles) against nothing rematerialised: same loss,
    same gradient on every leaf, to float32 rounding. Under ``bf16_train``
    a second forward does not round as the first did (XLA keeps float32
    inside fused expressions), so ANY rematerialised step stands 2e-3 from
    the plain one, with the policy and without (PERF.md section 6, PR 38):
    there the policy step is held, leaf by leaf, to the checkpoint without
    a policy: a kept value is the value the second forward would have
    made."""
    model = _model(variant, precision)
    variables = model.init(jax.random.PRNGKey(3), (1,) + HW + (3,))
    batch = _batch()
    loss_r, grads_r = _loss_fn(model, variables, batch, True)(variables["params"])
    if precision == "f32":
        remat_too = False
    else:
        monkeypatch.setattr(raft_module, "save_named", None)  # jax.checkpoint(step)
        remat_too = True
    loss_o, grads_o = _loss_fn(model, variables, batch, remat_too)(variables["params"])
    assert float(loss_r) == pytest.approx(float(loss_o), rel=1e-6)
    flat_o = jax.tree.leaves(grads_o)
    scale = max(float(jnp.max(jnp.abs(g))) for g in flat_o)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads_r), flat_o):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6 * scale,
            err_msg=jax.tree_util.keystr(path),
        )


def _rematted_ops(lowered) -> list:
    """``op_name`` paths below ``rematted_computation`` in a lowered
    step: what the backward computes a second time."""
    text = lowered.as_text(debug_info=True)
    return [
        m.split("rematted_computation/", 1)[1]
        for m in re.findall(r'loc\("([^"]*rematted_computation/[^"]*)"', text)
    ]


def test_backward_holds_no_second_lookup_contraction_or_weights_net_product():
    """The policy by name on the checkpointed scan body (utils/remat.py):
    of the lookup the backward recomputes the axis weights alone (the
    volume's cotangent needs nothing else: the coordinates are detached),
    of the weights net the head's product alone; the update block's
    products it still computes again."""
    model = _model("raft_nc_dbl", "f32")
    variables = model.init(jax.random.PRNGKey(3), (1,) + HW + (3,))
    remat.reset_saved_residuals()
    again = _rematted_ops(
        _loss_fn(model, variables, _batch(), True).lower(variables["params"])
    )
    assert remat.saved_residuals() == {remat.LOOKUP_OUT: 1, remat.WEIGHTS_NET_CONV: 2}
    lookup = [op for op in again if op.startswith("raft.corr_lookup/")]
    assert lookup and not [
        op for op in lookup if re.search(r"reduce_sum|dot_general|/mul$", op)
    ]
    net = [op for op in again if "weights_est_net" in op and "conv_general" in op]
    assert net and all("/out/" in op for op in net)
    assert [op for op in again if op.startswith("raft.update_block/") and "conv_general" in op]
    # the baseline's head has no weights net: one name is never produced
    model = _model("raft", "f32")
    variables = model.init(jax.random.PRNGKey(3), (1,) + HW + (3,))
    remat.reset_saved_residuals()
    _loss_fn(model, variables, _batch(), True).lower(variables["params"])
    assert remat.saved_residuals() == {remat.LOOKUP_OUT: 1, remat.WEIGHTS_NET_CONV: 0}


def test_only_the_training_forward_is_rematerialised(monkeypatch):
    """The inference programs are what they were: no checkpoint in a
    ``test_mode`` forward, and the names lower to nothing (the module is
    the same text with them and without, to the number jax gives a private
    function's symbol, which counts the equations before it); the training
    forward has the loop body's and the two encoders' (each nested: outer,
    and inner with the policy)."""
    model = RAFT(flagship_config(dataset="sintel"))
    variables = model.init(jax.random.PRNGKey(3), (1,) + HW + (3,))
    img = jnp.zeros((1,) + HW + (3,), jnp.float32)

    def infer():  # a function of its own each time: jax caches traces
        return lambda v, a, b: model.apply(v, a, b, iters=2, test_mode=True)

    def lowered():  # but for the counter in a private function's symbol
        text = jax.jit(infer()).lower(variables, img, img).as_text()
        return re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1", text)

    jaxpr = str(jax.make_jaxpr(infer())(variables, img, img))
    assert "remat" not in jaxpr and "checkpoint" not in jaxpr and "name[" in jaxpr
    remat.reset_saved_residuals()
    named = lowered()
    assert not any(remat.saved_residuals().values())
    for module in (raft_module, weights_est):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    assert "name[" not in str(jax.make_jaxpr(infer())(variables, img, img))
    assert lowered() == named
    train = str(jax.make_jaxpr(
        lambda v, a, b: model.apply(v, a, b, iters=2, train=True, freeze_bn=True))(variables, img, img))
    assert train.count("remat2[") == 5
    plain = str(jax.make_jaxpr(
        lambda v, a, b: model.apply(v, a, b, iters=2, train=True, freeze_bn=True, remat=False))(variables, img, img))
    assert "remat" not in plain
