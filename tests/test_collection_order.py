"""The hand-out order of the suite's files (tests/conftest.py).

`LONGEST_FILES_FIRST` and `CPU_BUDGETED_FILES_LAST` are lists kept by
hand, so what can go stale in them is tested here: a listed file that is
gone, the two longest files losing the first two places, and xdist's own
reorder coming back.
"""

import os

import conftest
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The two files that are the floor under the suite's wall (966 and 965 s
# in the driver's run of PR 43's tree): each must have a worker from the
# first second.
FIRST_FILE = "tests/benchmark/test_train_mixed_cell.py"
AOT_FILE = "tests/test_tpu_aot_compile.py"


LISTED = conftest.LONGEST_FILES_FIRST + conftest.CPU_BUDGETED_FILES_LAST


@pytest.mark.parametrize("path", LISTED)
def test_every_listed_file_exists(path):
    assert os.path.isfile(os.path.join(REPO, path)), (
        f"{path} is listed in tests/conftest.py and is not in the tree: "
        "take it off the list"
    )


def test_no_file_is_listed_twice():
    assert len(set(LISTED)) == len(LISTED)


def test_the_two_longest_files_are_handed_out_first_and_unlisted_files_last():
    assert conftest.LONGEST_FILES_FIRST[:2] == (FIRST_FILE, AOT_FILE)
    assert conftest.file_rank(f"{FIRST_FILE}::test_x[f32]") == 0
    assert conftest.file_rank(f"{AOT_FILE}::test_x[f32]") == 1
    unlisted = len(conftest.LONGEST_FILES_FIRST)
    assert conftest.file_rank("tests/test_serving.py::test_y") == unlisted
    assert conftest.file_rank("tests/test_fleet.py::TestA::test_z") == unlisted
    for path in conftest.CPU_BUDGETED_FILES_LAST:
        assert conftest.file_rank(f"{path}::test_w") > unlisted


def test_the_sort_keeps_the_order_inside_a_file(request):
    """`pytest_collection_modifyitems` on stand-in items: listed files
    come first in the list's order, the CPU-budgeted file last, and
    neither the tests of one file nor the unlisted files change places
    among themselves."""

    class Item:
        def __init__(self, nodeid):
            self.nodeid = nodeid

    ids = [
        "tests/test_lint.py::b", "tests/test_traffic.py::b",
        "tests/test_fleet.py::a", f"{AOT_FILE}::t2", f"{FIRST_FILE}::k2",
        "tests/test_lint.py::a", "tests/test_traffic.py::a",
        f"{AOT_FILE}::t1", f"{FIRST_FILE}::k1",
    ]
    items = [Item(i) for i in ids]
    conftest.pytest_collection_modifyitems(request.config, items)
    assert [i.nodeid for i in items] == [
        f"{FIRST_FILE}::k2", f"{FIRST_FILE}::k1", f"{AOT_FILE}::t2",
        f"{AOT_FILE}::t1", "tests/test_traffic.py::b",
        "tests/test_fleet.py::a", "tests/test_traffic.py::a",
        "tests/test_lint.py::b", "tests/test_lint.py::a",
    ]


def test_xdist_count_based_reorder_is_off(request):
    """With xdist loaded the queue must follow the collection order; the
    option is absent under `-p no:xdist`, where nothing reorders."""
    option = request.config.option
    if hasattr(option, "loadscopereorder"):
        assert option.loadscopereorder is False
