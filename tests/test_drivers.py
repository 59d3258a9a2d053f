"""Tests for the CLI bridge, evaluation functions, and the train driver."""

import os
import sys

import jax
import numpy as np
import pytest
from PIL import Image

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_ncup_tpu.cli import parse_eval, parse_train
from raft_ncup_tpu.config import small_model_config, TrainConfig, UpsamplerConfig
from raft_ncup_tpu.evaluation import (
    create_kitti_submission,
    validate_chairs,
    validate_kitti,
)
from raft_ncup_tpu.io import read_flow_kitti, write_flo, write_flow_kitti
from raft_ncup_tpu.models.raft import RAFT

# The exact flag block every shipped reference script passes
# (reference: train_raft_nc_things.sh:19-50).
REFERENCE_SCRIPT_FLAGS = [
    "--name", "raft_nc_things_ft",
    "--model", "raft_nc_dbl",
    "--stage", "things",
    "--validation", "sintel",
    "--compressed_ft",
    "--gpus", "0", "1",
    "--num_steps", "100000",
    "--batch_size", "6",
    "--lr", "0.000125",
    "--image_size", "400", "720",
    "--optimizer", "adamW",
    "--scheduler", "cyclic",
    "--final_upsampling=NConvUpsampler",
    "--final_upsampling_scale=4",
    "--final_upsampling_use_data_for_guidance=True",
    "--final_upsampling_channels_to_batch=True",
    "--final_upsampling_use_residuals=False",
    "--final_upsampling_est_on_high_res=False",
    "--interp_net=NConvUNet",
    "--interp_net_channels_multiplier=2",
    "--interp_net_num_downsampling=1",
    "--interp_net_data_pooling=conf_based",
    "--interp_net_encoder_filter_sz=5",
    "--interp_net_decoder_filter_sz=3",
    "--interp_net_out_filter_sz=1",
    "--interp_net_shared_encoder=True",
    "--interp_net_use_double_conv=False",
    "--interp_net_use_bias=False",
    "--weights_est_net=Simple",
    "--weights_est_net_num_ch=[64, 32]",
    "--weights_est_net_filter_sz=[3, 3, 1]",
    "--weights_est_net_dilation=[1, 1, 1]",
]


class TestCli:
    def test_reference_script_flags_resolve(self):
        args, model_cfg, train_cfg, data_cfg = parse_train(
            REFERENCE_SCRIPT_FLAGS
        )
        assert model_cfg.variant == "raft_nc_dbl"
        assert model_cfg.dataset == "things"  # BN off outside sintel
        ups = model_cfg.upsampler
        assert ups.kind == "nconv" and ups.scale == 4
        assert ups.weights_est_num_ch == (64, 32)
        assert ups.weights_est_filter_sz == (3, 3, 1)
        assert ups.shared_encoder and not ups.use_bias
        assert train_cfg.num_steps == 100000
        assert train_cfg.lr == pytest.approx(0.000125)
        assert train_cfg.image_size == (400, 720)
        assert train_cfg.optimizer == "adamw"
        assert train_cfg.validation == ("sintel",)
        assert data_cfg.compressed_ft

    def test_the_chairs_script_is_the_benchmarks_configuration(self):
        """``scripts/train_raft_chairs.sh`` (upstream RAFT's
        ``train_standard.sh``, first command) resolves to the ``train`` block
        of ``benchmark/configs/raft-chairs.json``, the deployment the cell
        ``train_chairs_raft`` measures, and to its model."""
        import json
        import shlex

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        text = open(os.path.join(root, "scripts", "train_raft_chairs.sh")).read()
        command = text[text.index("python -u train.py") + len("python -u train.py"):]
        flags = [
            t.replace("$EXP", "raft-chairs")
            for t in shlex.split(command.replace("\\\n", " ")) if t != "$@"
        ]
        assert "--gpus" not in flags and "--mixed_precision" not in flags
        _, model_cfg, train_cfg, _ = parse_train(flags)
        config = json.load(open(os.path.join(root, "benchmark", "configs", "raft-chairs.json")))
        want = config["train"]
        for key in ("stage", "lr", "num_steps", "batch_size", "iters", "wdecay", "epsilon",
                    "clip", "gamma", "max_flow", "optimizer", "scheduler", "add_noise", "sum_freq"):
            assert getattr(train_cfg, key) == want[key], key
        assert list(train_cfg.image_size) == want["image_size"]
        assert train_cfg.validation == ("chairs",) and train_cfg.precision == config["model"]["precision"]
        assert (train_cfg.stage != "chairs") is want["freeze_bn"]  # parallel/step.py's rule
        assert model_cfg.freeze_raft is want["freeze_raft"]
        for key in ("variant", "small", "corr_impl", "corr_levels", "corr_radius", "precision"):
            assert getattr(model_cfg, key) == config["model"][key], key

    def test_eval_parser(self):
        args, model_cfg, data_cfg = parse_eval(
            ["--model", "raft_nc_dbl", "--dataset", "sintel",
             "--restore_ckpt", "x"]
        )
        assert model_cfg.dataset == "sintel"  # upsampler BN on for sintel
        assert args.dataset == "sintel"

    def test_upsampler_bi_overrides(self):
        _, model_cfg, *_ = parse_train(
            ["--stage", "chairs", "--model", "raft_nc_dbl", "--upsampler_bi"]
        )
        assert model_cfg.upsampler.kind == "bilinear"


    @pytest.mark.parametrize("name", ["PacJointUpsampleFull", "DjifOriginal"])
    def test_a_head_that_left_the_tree_is_a_usage_error(self, name, capsys):
        """The reference's other two upsampler class names stop at parse
        time, and the message lists the two that are built."""
        with pytest.raises(SystemExit) as e:
            parse_train(
                ["--stage", "chairs", "--model", "raft_nc_dbl",
                 f"--final_upsampling={name}"]
            )
        assert e.value.code == 2
        err = capsys.readouterr().err
        assert "--final_upsampling" in err and name in err
        assert "choose from" in err
        assert "Bilinear" in err and "NConvUpsampler" in err


# ------------------------------------------------------------------ fixtures


def make_chairs_fixture(root, n=3, hw=(48, 64)):
    root.mkdir(parents=True)
    g = np.random.default_rng(0)
    for i in range(1, n + 1):
        for k in (1, 2):
            Image.fromarray(
                g.integers(0, 255, (*hw, 3), dtype=np.uint8)
            ).save(root / f"{i:05d}_img{k}.png")
        write_flo(
            root / f"{i:05d}_flow.flo",
            g.normal(size=(*hw, 2)).astype(np.float32),
        )
    split_file = root.parent / "chairs_split.txt"
    np.savetxt(split_file, np.full(n, 2), fmt="%d")  # all validation
    return split_file


def make_kitti_fixture(root, split, n=2, hw=(48, 64), sizes=None):
    """``sizes``: one (h, w) a frame (KITTI's native sizes differ from
    drive to drive); default ``n`` frames of ``hw``."""
    sizes = sizes or [hw] * n
    d = root / split
    (d / "image_2").mkdir(parents=True)
    g = np.random.default_rng(1)
    for i, hw in enumerate(sizes):
        for suffix in ("10", "11"):
            Image.fromarray(
                g.integers(0, 255, (*hw, 3), dtype=np.uint8)
            ).save(d / "image_2" / f"{i:06d}_{suffix}.png")
    if split == "training":
        (d / "flow_occ").mkdir(parents=True)
        for i, hw in enumerate(sizes):
            write_flow_kitti(
                d / "flow_occ" / f"{i:06d}_10.png",
                g.normal(size=(*hw, 2)).astype(np.float32),
            )


def make_sintel_fixture(root, hw=(48, 64), frames=3):
    """training split (clean+final+flow) and test split (images only)."""
    g = np.random.default_rng(5)
    for split, dstypes in (("training", ("clean", "final")),
                           ("test", ("clean", "final"))):
        for dstype in dstypes:
            d = root / split / dstype / "scene_x"
            d.mkdir(parents=True, exist_ok=True)
            for i in range(frames):
                Image.fromarray(
                    g.integers(0, 255, (*hw, 3), dtype=np.uint8)
                ).save(d / f"frame_{i:04d}.png")
    fd = root / "training" / "flow" / "scene_x"
    fd.mkdir(parents=True)
    for i in range(frames - 1):
        write_flo(
            fd / f"frame_{i:04d}.flo",
            g.normal(size=(*hw, 2)).astype(np.float32),
        )


@pytest.fixture(scope="module")
def tiny_raft():
    cfg = small_model_config("raft", dataset="chairs")
    model = RAFT(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, 48, 64, 3))
    return model, variables


class TestEvaluation:
    def test_validate_chairs(self, tmp_path, tiny_raft):
        from raft_ncup_tpu.config import DataConfig

        split_file = make_chairs_fixture(tmp_path / "chairs")
        model, variables = tiny_raft
        cfg = DataConfig(
            root_chairs=str(tmp_path / "chairs"),
            chairs_split_file=str(split_file),
        )
        out = validate_chairs(model, variables, cfg, iters=2)
        assert "chairs" in out and np.isfinite(out["chairs"])

    def test_validate_kitti(self, tmp_path, tiny_raft):
        from raft_ncup_tpu.config import DataConfig

        make_kitti_fixture(tmp_path / "KITTI", "training")
        model, variables = tiny_raft
        cfg = DataConfig(root_kitti=str(tmp_path / "KITTI"))
        out = validate_kitti(model, variables, cfg, iters=2)
        assert np.isfinite(out["kitti-epe"])
        assert 0.0 <= out["kitti-f1"] <= 100.0

    def test_validate_kitti_over_mixed_sizes_is_the_pair_at_a_time_pass(
        self, tmp_path, tiny_raft
    ):
        """Five frames of two native sizes, alternating, at batch 2: the
        pass groups them by size across the stream and fills each
        remainder, and the numbers are upstream's one-pair-at-a-time
        numbers to float32 summation order."""
        from raft_ncup_tpu.config import DataConfig

        a, b = (45, 61), (43, 64)  # both pad to 48x64
        make_kitti_fixture(tmp_path / "KITTI", "training", sizes=[a, b, a, b, a])
        model, variables = tiny_raft
        cfg = DataConfig(root_kitti=str(tmp_path / "KITTI"))
        one = validate_kitti(model, variables, cfg, iters=2, batch_size=1)
        two = validate_kitti(model, variables, cfg, iters=2, batch_size=2)
        assert two["kitti-epe"] == pytest.approx(one["kitti-epe"], rel=1e-5)
        assert two["kitti-f1"] == pytest.approx(one["kitti-f1"], rel=1e-6)

    def test_validate_sintel_and_submission(self, tmp_path, tiny_raft):
        from raft_ncup_tpu.config import DataConfig
        from raft_ncup_tpu.evaluation import (
            create_sintel_submission,
            validate_sintel,
        )
        from raft_ncup_tpu.io import read_flo

        make_sintel_fixture(tmp_path / "Sintel")
        model, variables = tiny_raft
        cfg = DataConfig(root_sintel=str(tmp_path / "Sintel"))
        out = validate_sintel(model, variables, cfg, iters=2)
        assert np.isfinite(out["clean"]) and np.isfinite(out["final"])
        assert 0.0 <= out["clean_1px"] <= 1.0

        sub = tmp_path / "sub"
        create_sintel_submission(
            model, variables, cfg, iters=2, warm_start=True,
            output_path=str(sub),
        )
        flo = sub / "clean" / "scene_x" / "frame0001.flo"
        assert flo.exists()
        assert read_flo(flo).shape == (48, 64, 2)

    def test_kitti_submission_roundtrip(self, tmp_path, tiny_raft):
        from raft_ncup_tpu.config import DataConfig

        make_kitti_fixture(tmp_path / "KITTI", "testing")
        model, variables = tiny_raft
        cfg = DataConfig(root_kitti=str(tmp_path / "KITTI"))
        out_dir = tmp_path / "subm"
        create_kitti_submission(
            model, variables, cfg, iters=2, output_path=str(out_dir)
        )
        files = sorted(os.listdir(out_dir))
        assert files == ["000000_10.png", "000001_10.png"]
        flow, valid = read_flow_kitti(out_dir / files[0])
        assert flow.shape == (48, 64, 2)
        assert valid.all()


class TestEvalDriverMesh:
    def test_evaluate_cli_spatial_parallel(self, tmp_path, capsys):
        """VERDICT r3 #7: the driver-flag path for spatially-sharded eval
        — evaluate.py --spatial_parallel 2 — end-to-end over a Sintel
        fixture, and numerically equal to the single-device CLI run.
        Reference driver anchor: evaluate.py:111-143."""
        import evaluate as eval_driver

        make_sintel_fixture(tmp_path / "Sintel")
        base = [
            "--model", "raft", "--small",
            "--dataset", "sintel",
            "--corr_impl", "onthefly",
            "--iters", "2",
            "--root_sintel", str(tmp_path / "Sintel"),
        ]
        eval_driver.main(base)
        single = capsys.readouterr().out.strip().splitlines()[-1]
        eval_driver.main(base + ["--spatial_parallel", "2"])
        sharded = capsys.readouterr().out.strip().splitlines()[-1]
        # Both runs print the validator dict; EPEs must match closely.
        import ast

        s1, s2 = ast.literal_eval(single), ast.literal_eval(sharded)
        assert np.isfinite(s2["clean"]) and np.isfinite(s2["final"])
        np.testing.assert_allclose(s2["clean"], s1["clean"], rtol=1e-4)
        np.testing.assert_allclose(s2["final"], s1["final"], rtol=1e-4)


class TestDemoDriver:
    def test_demo_writes_flow_visualizations(self, tmp_path, capsys):
        """demo.py end-to-end: folder of frames in, side-by-side flow
        pngs out (reference: demo.py:50-68; C18)."""
        import demo as demo_driver

        frames = tmp_path / "frames"
        frames.mkdir()
        g = np.random.default_rng(9)
        for i in range(3):
            Image.fromarray(
                g.integers(0, 255, (48, 64, 3), dtype=np.uint8)
            ).save(frames / f"frame_{i:02d}.png")
        out = tmp_path / "out"
        demo_driver.main([
            "--path", str(frames), "--output", str(out),
            "--model", "raft", "--small", "--iters", "2",
        ])
        written = sorted(os.listdir(out))
        assert written == ["frame_00_flow.png", "frame_01_flow.png"]
        vis = np.asarray(Image.open(out / written[0]))
        # Side-by-side stack: frame on top, colorized flow below.
        assert vis.shape == (96, 64, 3)


class TestTrainDriver:
    # Tier-2: ~47s (two full train.py main() invocations). Resume
    # correctness stays tier-1 via test_checkpoint.py and the chaos
    # preemption tests; this CLI-level composition runs unfiltered.
    @pytest.mark.slow
    def test_train_resume_cycle(self, tmp_path, monkeypatch):
        """End-to-end composition through ``main(argv)``: loader, val
        cadence, checkpoint, restore (reference: train.py:167-261)."""
        import train as train_driver
        from raft_ncup_tpu import evaluation as eval_mod

        # Record the validation hook instead of scanning real datasets.
        val_calls: list[int] = []

        def fake_validator(model, variables, data_cfg=None):
            val_calls.append(1)
            return {"chairs_epe": 0.0}

        monkeypatch.setitem(eval_mod.VALIDATORS, "chairs", fake_validator)

        monkeypatch.chdir(tmp_path)
        base = [
            "--name", "smoke",
            "--model", "raft",
            "--small",
            "--stage", "chairs",
            "--image_size", "32", "48",
            "--batch_size", "2",
            "--iters", "2",
            "--val_freq", "2",
            "--sum_freq", "1",
            "--validation", "chairs",
            "--synthetic_ok",
            "--num_workers", "1",
            "--root_chairs", str(tmp_path / "missing"),
        ]
        train_driver.main(base + ["--num_steps", "3"])
        run_dir = tmp_path / "checkpoints" / "smoke"
        assert (run_dir / "log.txt").exists()
        steps = [d for d in os.listdir(run_dir) if d.isdigit()]
        assert "3" in steps
        # val_freq=2 with 3 steps: validation at steps 2 and 3 (final).
        assert len(val_calls) == 2
        log = (run_dir / "log.txt").read_text()
        assert "chairs_epe" in log

        # Resume from the saved state and run 2 more steps.
        train_driver.main(
            base + ["--num_steps", "5", "--restore_ckpt", str(run_dir)]
        )
        steps = {d for d in os.listdir(run_dir) if d.isdigit()}
        assert "5" in steps
        log = (run_dir / "log.txt").read_text()
        assert "restored step 3" in log

    def test_train_cli_mesh_flags(self, tmp_path, monkeypatch):
        """The driver-flag multichip path: train.py --data_parallel 2
        --spatial_parallel 2 builds a (2 x 2) mesh over the virtual
        devices and trains on it (reference's 2-GPU DataParallel
        analogue, train.py:169-175)."""
        import train as train_driver

        monkeypatch.chdir(tmp_path)
        train_driver.main([
            "--name", "mesh_smoke",
            "--model", "raft",
            "--small",
            "--stage", "chairs",
            "--image_size", "32", "48",
            "--batch_size", "2",
            "--iters", "2",
            "--num_steps", "2",
            "--sum_freq", "1",
            "--synthetic_ok",
            "--num_workers", "1",
            "--data_parallel", "2",
            "--spatial_parallel", "2",
            "--root_chairs", str(tmp_path / "missing"),
        ])
        run_dir = tmp_path / "checkpoints" / "mesh_smoke"
        log = (run_dir / "log.txt").read_text()
        assert "mesh=(2 data x 2 spatial)" in log
        assert (run_dir / "2").exists()


def test_validate_synthetic_heldout():
    """The synthetic validator runs on a held-out procedural split and
    returns a finite EPE for an untrained model."""
    import jax

    from raft_ncup_tpu.config import small_model_config
    from raft_ncup_tpu.evaluation import validate_synthetic
    from raft_ncup_tpu.models import get_model

    model = get_model(small_model_config("raft", dataset="chairs"))
    variables = model.init(jax.random.PRNGKey(0), (1, 32, 48, 3))
    out = validate_synthetic(
        model, variables, iters=2, batch_size=2, size_hw=(32, 48), length=4
    )
    assert set(out) == {"synthetic"}
    assert np.isfinite(out["synthetic"])


def test_validate_synthetic_empty_shard_skips():
    """Agreed length 0 (empty host shard) must skip like the real-data
    validators, not divide by zero — the guard fires before any forward,
    so model/variables are never touched."""
    from raft_ncup_tpu.evaluation import validate_synthetic

    out = validate_synthetic(None, {}, iters=2, batch_size=2,
                             size_hw=(32, 48), length=0)
    assert out == {}


def test_validate_synthetic_spatial_mesh_matches():
    """The mesh-sharded eval path (evaluate.py --spatial_parallel) must
    reproduce the single-device validator EPE."""
    import jax

    from raft_ncup_tpu.config import small_model_config
    from raft_ncup_tpu.evaluation import validate_synthetic
    from raft_ncup_tpu.models import get_model
    from raft_ncup_tpu.parallel.mesh import make_mesh

    model = get_model(
        small_model_config("raft", dataset="chairs", corr_impl="onthefly")
    )
    variables = model.init(jax.random.PRNGKey(0), (1, 32, 48, 3))
    kwargs = dict(iters=2, batch_size=2, size_hw=(32, 48), length=4)
    ref = validate_synthetic(model, variables, **kwargs)
    mesh = make_mesh(data=1, spatial=2, devices=jax.devices()[:2])
    out = validate_synthetic(model, variables, mesh=mesh, **kwargs)
    np.testing.assert_allclose(out["synthetic"], ref["synthetic"], rtol=1e-4)


class TestServeDriver:
    def test_sigterm_drain_leaves_one_flight_dump_and_healthz(
        self, tmp_path, capsys, monkeypatch
    ):
        """The rc-75 half of the flight-recorder acceptance through the
        REAL driver: a serve.py run SIGTERMed mid-stream drains (exit
        75), leaves EXACTLY one valid preemption_drain dump, rewrites
        the --healthz_file to draining, and scripts/postmortem.py
        reassembles a served request's full span journey (queue wait →
        dispatch → drain) from the dump."""
        import importlib.util
        import json

        import serve as serve_driver
        from raft_ncup_tpu.observability import get_telemetry, set_telemetry

        flight = tmp_path / "flight"
        healthz = tmp_path / "healthz.json"
        # The driver arms the PROCESS hub; isolate it from other tests.
        prev = set_telemetry(None)
        try:
            rc = serve_driver.main([
                "--platform", "cpu",
                "--small",
                "--num_requests", "8",
                "--size", "48", "64",
                "--iter_levels", "2,1",
                "--serve_batch_sizes", "1,2",
                "--chaos", "sigterm@3",
                "--flight_dir", str(flight),
                "--healthz_file", str(healthz),
                "--telemetry_interval_s", "0.5",
            ])
        finally:
            tel = get_telemetry()
            tel.flight = None
            tel.slo = None
            set_telemetry(prev)
        assert rc == 75  # EXIT_PREEMPTED: the SIGTERM/exit-75 contract
        out = capsys.readouterr().out
        report = json.loads(out.strip().splitlines()[-1])
        assert report["interrupted"] is True
        assert report["health"]["state"] == "draining"
        assert "slo" in report
        hz = json.load(open(healthz))
        assert hz["draining"] is True and hz["overall"] == "draining"
        dumps = sorted(os.listdir(flight))
        assert len(dumps) == 1 and dumps[0].startswith(
            "flight_preemption_drain_"
        )
        spec = importlib.util.spec_from_file_location(
            "postmortem",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "scripts", "postmortem.py",
            ),
        )
        pm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pm)
        assert pm.main([str(flight / dumps[0]), "--request_id", "0"]) == 0
        journey = capsys.readouterr().out
        for stage in ("serve_queue_wait", "serve_dispatch", "serve_drain"):
            assert stage in journey  # the request's full span journey


class TestReplicaDriver:
    def test_replica_mode_serves_wire_and_drains_on_sigterm(
        self, tmp_path, capsys
    ):
        """The fleet replica half of the drain contract through the
        REAL driver, in-process: serve.py --replica_socket answers a
        request and a stream frame over the wire protocol, advertises
        its identity (warmed executable set) through --healthz_file,
        and on SIGTERM shows DRAINING in healthz, flushes, exits 75
        with guard counters 0 (docs/FLEET.md)."""
        import json
        import signal
        import socket
        import threading
        import time

        import serve as serve_driver
        from raft_ncup_tpu.fleet.wire import recv_msg, send_msg
        from raft_ncup_tpu.observability import get_telemetry, set_telemetry

        sock_path = str(tmp_path / "replica.sock")
        healthz = tmp_path / "healthz.json"
        client_out = {}

        def client():
            deadline = time.monotonic() + 120
            while not os.path.exists(sock_path):
                if time.monotonic() > deadline:
                    client_out["error"] = "socket never appeared"
                    os.kill(os.getpid(), signal.SIGTERM)
                    return
                time.sleep(0.05)
            img = np.random.default_rng(0).uniform(
                0, 255, (48, 64, 3)
            ).astype(np.float32)
            try:
                s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                s.connect(sock_path)
                send_msg(s, {"kind": "request", "id": 5}, [img, img])
                hdr, arrs = recv_msg(s)
                client_out["request"] = (hdr, arrs[0].shape if arrs else None)
                send_msg(s, {"kind": "frame", "id": 6, "stream_id": "sA",
                             "frame_index": 0}, [img, img])
                hdr, arrs = recv_msg(s)
                client_out["frame"] = (hdr, arrs[0].shape if arrs else None)
                client_out["healthz_live"] = json.load(open(healthz))
            except Exception as e:  # surfaced via the asserts below
                client_out["error"] = repr(e)
            finally:
                os.kill(os.getpid(), signal.SIGTERM)

        prev = set_telemetry(None)
        t = threading.Thread(target=client, daemon=True)
        try:
            t.start()
            rc = serve_driver.main([
                "--platform", "cpu", "--small",
                "--replica_socket", sock_path,
                "--replica_index", "2",
                "--size", "48", "64",
                "--iter_levels", "2",
                "--serve_batch_sizes", "1,2",
                "--replica_streams", "true",
                "--stream_capacity", "2",
                "--stream_iters", "2",
                "--stream_batch_sizes", "1,2",
                "--healthz_file", str(healthz),
                "--flight_dir", str(tmp_path / "flight"),
                "--telemetry_interval_s", "0.25",
            ])
            t.join(timeout=30)
        finally:
            tel = get_telemetry()
            tel.flight = None
            tel.slo = None
            tel.identity.clear()
            set_telemetry(prev)
        assert "error" not in client_out, client_out
        assert rc == 75  # the SIGTERM -> drain -> exit-75 contract
        hdr, flow_shape = client_out["request"]
        assert hdr["id"] == 5 and hdr["status"] == "ok"
        assert flow_shape == (48, 64, 2)
        hdr, flow_shape = client_out["frame"]
        assert hdr["id"] == 6 and hdr["status"] == "ok"
        assert flow_shape == (48, 64, 2)
        # Live healthz carried the replica identity the router routes on.
        live = client_out["healthz_live"]
        assert live["replica"] == 2
        assert [48, 64, 1, 2] in live["warmed"]
        assert live["stale_after_s"] == 0.5
        # Final healthz: DRAINING, per the contract.
        hz = json.load(open(healthz))
        assert hz["draining"] is True and hz["overall"] == "draining"
        # Final report: guard-clean window, every request accounted.
        out = capsys.readouterr().out
        report = json.loads(out.strip().splitlines()[-1])
        assert report["interrupted"] is True
        assert report["replica"] == 2
        assert report["recompiles"] == 0
        assert report["host_transfers"] == 0
        assert report["completed"] == 1
        assert report["stream_completed"] == 1
