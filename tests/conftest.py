"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip sharding (data x spatial meshes) is tested on virtual CPU
devices, mirroring how the driver dry-runs the multi-chip path
(``xla_force_host_platform_device_count``).
"""

import os

# Overwrite, not setdefault: whatever the caller's environment names,
# tests run on virtual CPU devices so the sharded paths can be exercised
# without a pod (the chip is reached only through chip_smoke.py).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax reads the variable when it is first imported; if a plugin imported it
# before this file ran, the config must be updated directly too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def forbid_host_transfers():
    """The runtime guard as a fixture: a context-manager factory.
    ``with forbid_host_transfers() as stats: ...`` raises GuardViolation
    on any implicit device->host pull inside the scope (explicit
    jax.device_get stays sanctioned)."""
    from raft_ncup_tpu.analysis.guards import forbid_host_transfers as fht

    return fht


@pytest.fixture
def max_recompiles():
    """Compile-budget guard as a fixture: ``with max_recompiles(1): ...``
    raises GuardViolation when the scope compiles more than n times."""
    from raft_ncup_tpu.analysis.guards import max_recompiles as mr

    return mr


# The order in which files are handed to the workers, decided here and
# nowhere else. The driver runs `-n 6 --dist loadfile`: a file is one unit
# of work, a worker takes the next unit of the queue when it has at most
# two tests left, and xdist's default queue is "most tests first", which
# hands the cheap many-test files out first and the files that compile
# whole programs minutes into the run, to end alone. So: the files that
# cost 60 s and more in the driver's junit file of PR 43's tree (7,107
# worker-seconds, 1238 s of wall) go first, longest first, and the rest
# follow in collection order (but see CPU_BUDGETED_FILES_LAST below).
# tests/benchmark/test_train_mixed_cell.py (966 s) and
# tests/test_tpu_aot_compile.py (965 s) are the floor under the wall
# whatever follows them: each has a worker to itself from the first second,
# and with more than two tests neither gets a second unit queued behind it
# at the start. Then 884, 518, 508, 444, 424, 408, 275, 210, 174, 144 and
# 95 s; the last seven are 51-83 s each.
# tests/test_collection_order.py fails when a listed file is gone.
LONGEST_FILES_FIRST = (
    "tests/benchmark/test_train_mixed_cell.py",
    "tests/test_tpu_aot_compile.py",
    "tests/benchmark/test_train_cell.py",
    "tests/test_corr_pallas.py",
    "tests/benchmark/test_benchmark.py",
    "tests/test_chip_smoke.py",
    "tests/benchmark/test_1080p_cell.py",
    "tests/test_train_loop.py",
    "tests/benchmark/test_stream_cell.py",
    "tests/test_drivers.py",
    "tests/benchmark/test_chairs_cell.py",  # PR 49: 170 s alone
    "tests/test_nconv.py",
    "tests/benchmark/test_eval_mixed_cell.py",
    "tests/test_eval_staging.py",
    "tests/test_corr.py",
    "tests/test_chaos_train.py",
    "tests/test_multihost.py",
    "tests/test_checkpoint.py",
    "tests/test_earlyexit.py",
    "tests/test_mask_head.py",
    "tests/test_mesh_sharding.py",
    "tests/benchmark/test_kitti_cell.py",  # PR 45: 76 s alone
)


# ...and last, the file with a test that holds a CPU-time budget
# (tests/test_lint.py::test_whole_program_pass_stays_fast: 5 CPU-seconds
# for 2.3 alone). Beside six busy workers and the TPU compiler's threads
# that reading doubles; the end of the run, when most workers have nothing
# left, is as quiet as its old place at the start used to be.
CPU_BUDGETED_FILES_LAST = ("tests/test_lint.py",)

_RANK = {path: i for i, path in enumerate(LONGEST_FILES_FIRST)}
_RANK.update(
    (path, len(LONGEST_FILES_FIRST) + 1 + i)
    for i, path in enumerate(CPU_BUDGETED_FILES_LAST)
)


def file_rank(nodeid: str) -> int:
    """A test's place in the hand-out order, by its file: the index in
    LONGEST_FILES_FIRST, one rank after them all for an unlisted file,
    and after those the files of CPU_BUDGETED_FILES_LAST."""
    return _RANK.get(nodeid.split("::", 1)[0], len(LONGEST_FILES_FIRST))


def pytest_collection_modifyitems(config, items):
    # Stable: tests keep their order within a file, unlisted files theirs.
    items.sort(key=lambda item: file_rank(item.nodeid))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "reference: tests that import the read-only reference repo"
    )
    config.addinivalue_line("markers", "slow: long-running tests")
    # xdist's count-based reorder of the queue would undo the order above;
    # without xdist (`-p no:xdist`) the option does not exist.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
