"""The trace reduction: interval arithmetic and names, and the whole
reduction of a small trace recorded on a TPU v5e chip
(data/fixture.xplane.pb, made by data/record_trace.py)."""
import os

import bench_tiny as T  # noqa: F401
import pytest

from harness import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "fixture.xplane.pb")


def test_union_merges_overlaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [(0, 4), (5, 7), (8, 9)]


@pytest.mark.parametrize("module,name", [
    ("jit_fused_run(123)", "fused_run"), ("jit_fused_run", "fused_run"),
    ("jit__lambda_.4", "_lambda_"), ("prefill", "prefill"),
])
def test_program_name(module, name):
    assert xplane.program_name(module) == name


def test_op_name_drops_containers():
    assert xplane.op_name("%fusion.520 = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion.520"
    assert xplane.op_name("%while.13 = (s32[]) while((s32[]) %t), body=%b") is None
    assert xplane.op_name("%cond.94 = (f32[]) conditional(pred[] %p)") is None


def test_recorded_tpu_trace():
    """Three ``fixture_step`` executions 20 ms apart and one ``lambda``
    under ``bench.window`` (record_trace.py). On this trace the device
    clock runs about 1 ms behind the host's, so the first execution
    starts before the window's host annotation and is left out; over a
    benchmark window of seconds that is noise."""
    r = xplane.reduce(FIXTURE)
    assert r.chips == 1
    assert r.window_s == pytest.approx(0.06553644)
    assert r.program_calls == {"fixture_step": 2, "_lambda": 1}
    assert r.programs["fixture_step"] == pytest.approx((91501 + 91458) * 1e-9)
    assert r.programs["_lambda"] == pytest.approx(91438e-9)
    assert r.busy_s == pytest.approx((91501 + 91458 + 91438) * 1e-9)
    assert r.idle_share == pytest.approx(1 - r.busy_s / r.window_s)
    assert r.ops["convolution_tanh_fusion"] == pytest.approx(r.programs["fixture_step"], rel=1e-3)
    assert all(not op.startswith(("while", "cond")) for op in r.ops)
    lengths = [s for _, s in r.gaps]
    assert len(r.gaps) == 4 and lengths == sorted(lengths, reverse=True)
    assert 0.02 < lengths[0] < 0.025  # a 20 ms host sleep between calls
    assert r.gaps[0][0].startswith("bench.call")
    assert sum(lengths) == pytest.approx(r.window_s - r.busy_s)
