"""The check that decides ``correct``, driven through whole runs of the
tiny-moe-serve cell on the CPU with the chip look skipped: an MoE
configuration (one dense block, then routed and shared experts) added
to the test root as new files only. The sound program comes out
correct; the precision control (the program's own bfloat16 path) and
each fault the cell can have, planted in the timed path, do not."""
import bench_tiny as T
import pytest


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return T.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("fault", ["none", "control"] + T.fault_names("tiny-moe-serve"))
def test_check(root, fault):
    T.check_cell(root, "tiny-moe-serve", fault)
