"""CPU rehearsal of chip_smoke.py and of the rules it rests on.

chip_smoke.py itself only passes on a TPU (that is its point: it grows
no CPU switch). Its phase functions take their sizes as arguments, so
the control flow — report fields read, resume counted, the last line's
format, the refusal off-TPU — is rehearsed here at a toy size. Also
here: the compile-cache rule every entry point shares
(utils/runtime.enable_compilation_cache) and the peak-FLOP/s table keyed
by ``device_kind``.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

import chip_smoke as cs
from raft_ncup_tpu.utils import runtime
from raft_ncup_tpu.utils.profiling import compile_meter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_devices(n=1, platform="tpu", kind="TPU v5 lite"):
    return [
        SimpleNamespace(platform=platform, device_kind=kind, id=i)
        for i in range(n)
    ]


# ------------------------------------------------------------ device phase


def test_device_phase_refuses_a_non_tpu_platform():
    with pytest.raises(cs.SmokeFailure, match="no TPU"):
        cs.phase_device(jax.devices(), 1)  # the suite runs on the CPU


def test_device_phase_accepts_a_v5e_and_knows_its_peak():
    facts = cs.phase_device(_fake_devices(1), 1)
    assert facts == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        "peak_flops": 197e12,
    }


def test_device_phase_unknown_chip_is_an_error():
    with pytest.raises(KeyError, match="TPU v9"):
        cs.phase_device(_fake_devices(1, kind="TPU v9"), 1)


def test_device_phase_counts_chips():
    with pytest.raises(cs.SmokeFailure, match="needs 4"):
        cs.phase_device(_fake_devices(1), 4)
    assert cs.phase_device(_fake_devices(4), 4)["count"] == 4


def test_last_line_is_exactly_the_contract():
    line = cs.final_line(cs.device_record(_fake_devices(1)))
    assert line == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )
    assert "\n" not in line
    assert cs.final_line(cs.device_record(_fake_devices(4))).endswith(
        '"count": 4}}'
    )


def test_script_exits_nonzero_and_prints_no_result_off_tpu(tmp_path):
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` must FAIL at phase 1."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(tmp_path)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout


# ------------------------------------------------- phases at a toy size


def test_phase_context_prints_one_json_line(capsys):
    with cs.phase("toy", compile_meter()) as facts:
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.ones((3, 5)))
        facts["answer"] = 42
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["phase"] == "toy" and rec["answer"] == 42
    assert rec["compiles"] >= 1 and rec["compile_s"] > 0
    assert {"wall_s", "cache_hits", "cache_misses"} <= set(rec)


def test_phase_prints_nothing_when_its_body_fails(capsys):
    with pytest.raises(cs.SmokeFailure):
        with cs.phase("toy", compile_meter()):
            cs.check(False, "boom")
    assert capsys.readouterr().out == ""


def test_eval_phase_toy():
    facts, variables, flow = cs.phase_eval(0, hw=(60, 90), iters=2)
    assert facts["padded_shape"] == [1, 64, 96, 3]  # InputPadder at work
    assert 0 <= facts["epe_volume_vs_onthefly_px"] < cs.EPE_BUDGET_PX
    assert flow.shape == (1, 60, 90, 2) and "params" in variables
    # all of NCUP's layers engaged the tap form (four layers, five call
    # sites: the shared encoder is traced twice), none the MXU
    assert facts["nconv_engines"] == {"fused": 0, "fallback": 0, "taps": 5, "mxu": 0}


def test_server_phase_toy(tmp_path):
    facts = cs.phase_server(
        0, str(tmp_path), hw=(40, 48), num_requests=4, iter_levels=(2, 1),
        batch_sizes=(1, 2), n_streams=2, frames_per_stream=2,
        stream_iters=2,
    )
    assert facts["serve_completed"] == 4
    assert facts["serve_executables"]["compiles"] == 4  # warm-up only
    assert facts["stream_completed"] == 4
    assert facts["stream_executables"]["compiles"] == 2
    assert not os.path.exists(os.path.join(REPO, "flight_recorder"))


def test_warmstart_phase_toy():
    from evaluate import load_variables

    variables = load_variables(*cs._flagship("volume"), None)
    facts = cs.phase_warmstart(0, variables, lr_hw=(12, 20), hw=(60, 90), iters=2)
    assert facts["splat_cells_differing_from_host"] == [0, 0, 0]
    assert facts["warm_vs_cold_mean_px"] > 1e-3
    assert facts["stream_cold_starts"] == 2


def test_warmstart_phase_fails_when_the_splat_is_wrong(monkeypatch):
    from raft_ncup_tpu.ops import warmstart

    monkeypatch.setattr(
        warmstart, "forward_interpolate_batch", lambda flow, chunk=1024: flow
    )
    with pytest.raises(cs.SmokeFailure, match="argmin or gather"):
        cs.phase_warmstart(0, None, lr_hw=(12, 20), hw=(60, 90), iters=2)


def test_server_phase_reads_the_report_not_the_return_code(
    tmp_path, monkeypatch
):
    """serve.main returns 0 even with errors > 0: the smoke must not."""
    import serve

    report = {
        "completed": 3, "errors": 1, "shed": 0, "rejected": 0,
        "timeouts": 0, "executables": {"compiles": 4},
    }

    def fake_main(argv):
        print(json.dumps(report))
        return 0

    monkeypatch.setattr(serve, "main", fake_main)
    with pytest.raises(cs.SmokeFailure, match="completed=3"):
        cs.phase_server(
            0, str(tmp_path), num_requests=4, iter_levels=(2, 1),
            batch_sizes=(1, 2),
        )


def test_trainer_phase_toy_preempt_then_resume(tmp_path):
    facts = cs.phase_trainer(str(tmp_path), 2, hw=(48, 64), iters=1)
    assert facts["resumed_step"] == 5
    assert len(facts["losses"]) == 5
    # Checkpoint payloads are removed, the log is kept.
    assert sorted(os.listdir(tmp_path)) == ["smoke_log.txt"]


def test_train_batch_is_the_largest_that_leaves_headroom():
    batch, why = cs.pick_train_batch(15.75)  # what a v5e reports
    assert batch == 2 and "6.2 GiB" in why
    assert cs.pick_train_batch(32.0)[0] == 6
    with pytest.raises(cs.SmokeFailure):
        cs.pick_train_batch(4.0)


def test_mesh_phase_toy_on_virtual_devices():
    """--chips 4's control flow on the conftest's virtual CPU devices:
    the (data=2, spatial=2) step agrees with the one-device step, holds
    all-reduces and halo permutes, and shards over four devices."""
    facts = cs.phase_mesh(jax.devices(), 2, hw=(64, 64), iters=1)
    assert facts["mesh"] == {"data": 2, "spatial": 2}
    assert len(facts["shard_devices"]) == 4
    assert facts["collectives"]["all-reduce"] > 0
    assert facts["collectives"]["collective-permute"] > 0


# ------------------------------------------------------ compile-cache rule


@pytest.fixture
def cache_config():
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_env_set_means_no_directory_set_in_code(
    monkeypatch, cache_config
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compilation_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before  # untouched


def test_cache_unset_on_cpu_stays_off(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_unset_on_an_accelerator_is_the_fixed_checkout_path(
    monkeypatch, cache_config
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path = runtime.enable_compilation_cache()
    assert path == os.path.join(REPO, ".cache", "xla")
    assert jax.config.jax_compilation_cache_dir == path


def test_cache_path_is_the_same_in_two_processes():
    code = (
        "from raft_ncup_tpu.utils import runtime; "
        "print(runtime.DEFAULT_CACHE_DIR)"
    )
    paths = {
        subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, capture_output=True,
            text=True, check=True, timeout=120,
        ).stdout.strip()
        for _ in range(2)
    }
    assert paths == {os.path.join(REPO, ".cache", "xla")}


def test_nothing_in_the_runtime_module_deletes_the_cache():
    with open(runtime.__file__) as f:
        src = f.read()
    assert "rmtree" not in src and "os.remove" not in src


# ------------------------------------------------------------- peak table


def test_peak_flops_is_keyed_by_device_kind():
    from raft_ncup_tpu.inference.costs import peak_flops

    assert peak_flops("tpu", "TPU v5 lite") == 197e12


def test_peak_flops_unknown_tpu_kind_raises():
    from raft_ncup_tpu.inference.costs import peak_flops

    with pytest.raises(KeyError, match="no peak"):
        peak_flops("tpu", "TPU v5 lite pod-of-the-future")
    with pytest.raises(KeyError):
        peak_flops("tpu", None)
