"""Tests for the shared runtime/platform facts module
(raft_ncup_tpu.utils.runtime): platform forcing, the TPU check the
kernels dispatch on, and where the compile cache lives (the rule itself
is exercised in tests/test_chip_smoke.py).
"""

import os

from raft_ncup_tpu.utils import runtime


def test_host_fingerprint_stable_and_short():
    fp = runtime.host_fingerprint()
    assert fp == runtime.host_fingerprint()
    assert len(fp) == 8
    int(fp, 16)  # hex


def test_default_cache_dir_is_fixed_under_the_checkout():
    """The directory is part of the cache key: no host fingerprint, pid,
    time or temp component may enter it, or a fresh machine never hits."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert runtime.DEFAULT_CACHE_DIR == os.path.join(repo, ".cache", "xla")
    assert runtime.host_fingerprint() not in runtime.DEFAULT_CACHE_DIR
    assert str(os.getpid()) not in runtime.DEFAULT_CACHE_DIR


def test_no_cache_wipe_left_in_the_module():
    assert not hasattr(runtime, "wipe_compilation_cache_for_retry")


def test_force_platform_writes_env_and_config(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    runtime.force_platform("cpu")
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert jax.config.jax_platforms == "cpu"


def test_force_platform_after_backend_init_says_it_cannot_apply(monkeypatch):
    """ADVICE round 5 (then `__graft_entry__.py`'s re-point, now this
    function): once a backend is initialised `jax_platforms` is read no
    more, so a re-point to ANOTHER platform must fail loudly and leave
    env and config alone; the platform the process already has is fine."""
    import jax
    import pytest

    jax.devices()  # the conftest's cpu backend, initialised
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(RuntimeError, match="cannot apply.*'cpu' backend"):
        runtime.force_platform("tpu")
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert jax.config.jax_platforms == "cpu"
    runtime.force_platform("cpu")  # no re-point: nothing to refuse


def test_tpu_backend_check_is_an_equality_not_a_denylist(monkeypatch):
    import jax

    # The conftest forces the cpu backend for the whole suite.
    assert not runtime.is_tpu_backend()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.is_tpu_backend()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert not runtime.is_tpu_backend()
