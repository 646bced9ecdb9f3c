"""The program's spans against the chip's idle time: interval arithmetic,
and the reduction of a small trace recorded on a TPU v5e chip
(data/spans.xplane.pb and data/spans.events.json, made by
data/record_spans.py), with known host sleeps inside and outside the
program's spans."""
import json
import os
import shutil
import types

import bench_tiny as T
import pytest

from harness import spans, spec as S, xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = os.path.join(DATA, "spans.xplane.pb")
MS = 1e6  # ns
METRICS = ("walk.tune_idle_ms_per_block", "walk.stream_idle_ms_per_block",
           "walk.builds_per_block", "serve.dispatch_idle_ms_per_step",
           "serve.sync_idle_ms_per_step")


def events():
    with open(os.path.join(DATA, "spans.events.json")) as f:
        d = json.load(f)
    return [tuple(e) for e in d["events"]], d["wall0"]


@pytest.fixture(scope="module")
def sp():
    evs, wall0 = events()
    return spans.reduce(FIXTURE, evs, wall0)


def reader(name):
    return S.load_module(os.path.join(T.BENCH, "metrics", f"{name}.py"),
                         f"bench_metric_{name}")


def run_view(reduced, **counts):
    """A run view that already holds its spans, as ``spans.of`` leaves it."""
    return types.SimpleNamespace(spans=reduced, counts=counts)


def one(sp, name):
    found = sp.named(name)
    assert len(found) == 1, (name, found)
    return found[0]


def synthetic(busy, window=(0.0, 100.0), names=()):
    return spans.Spans(window=window, busy=busy, builds=[], offset_ns=0.0,
                       spans=[spans.Span(n, a, b, {}, "t") for n, a, b in names])


def test_busy_between_clips_partial_intervals():
    s = synthetic([(10.0, 20.0), (30.0, 40.0), (50.0, 60.0)])
    assert s.busy_ns(0, 100) == 30
    assert s.busy_ns(15, 35) == 5 + 5
    assert s.busy_ns(12, 18) == 6
    assert s.busy_ns(20, 30) == 0
    assert s.busy_ns(41, 49) == 0
    assert s.busy_ns(55, 200) == 5
    assert s.busy_ns(35, 35) == 0
    assert synthetic([]).busy_ns(0, 10) == 0


def test_idle_in_a_span_is_clipped_to_the_window():
    s = synthetic([(10.0, 20.0)], window=(5.0, 50.0),
                  names=[("walk/tune", 0.0, 30.0), ("walk/tune", 40.0, 90.0)])
    a, b = s.spans
    assert s.idle_ns(a) == (30 - 5) - 10
    assert s.idle_ns(b) == 50 - 40
    assert s.idle_s(("walk/tune",)) == pytest.approx((15 + 10) * 1e-9)


def test_each_group_of_programs_takes_its_own_clock_offset():
    """The smallest shift under which no program of a group starts before
    its enqueue; a group without a matched enqueue keeps the last one."""
    g = spans.GROUP
    runs = [(100.0 * k, 100.0 * k + 10, k) for k in range(2 * g + 5)]
    enq = {k: 100.0 * k + (7.0 if k == 3 else 2.0) for k in range(g)}
    enq.update({k: 100.0 * k - 1.0 for k in range(g, 2 * g)})
    shifted, offsets = spans.on_host_clock(runs, enq)
    assert offsets == [7.0, -1.0]
    assert shifted[0] == (7.0, 17.0)
    assert shifted[g] == (100.0 * g - 1, 100.0 * g + 9)
    assert shifted[2 * g + 4] == (100.0 * (2 * g + 4) - 1, 100.0 * (2 * g + 4) + 9)
    assert spans.on_host_clock(runs[:3], {}) == ([(0.0, 10.0), (100.0, 110.0),
                                                   (200.0, 210.0)], [])


def test_recorded_spans_and_parents(sp):
    names = [(s.name, s.attrs) for s in sp.spans]
    assert names == [("ebft/walk", {}), ("walk/setup", {}), ("walk/tune", {"block": 0}),
                     ("walk/student", {"block": 0}), ("serve/step", {}),
                     ("serve/sync", {})]
    walk, step = sp.spans.index(one(sp, "ebft/walk")), sp.spans.index(one(sp, "serve/step"))
    assert [s.parent for s in sp.spans] == [None, walk, walk, walk, None, step]
    assert sp.children(step, "serve/sync") == [one(sp, "serve/sync")]
    assert sp.children(walk, "serve/sync") == []


def test_device_clock_offset(sp):
    """The device clock of this trace runs about 1.2 ms behind the host's
    (PERF.md); after the shift no program starts before its enqueue, and
    the busy time is xplane.reduce's."""
    assert 0.5 * MS < sp.offset_ns < 2 * MS
    red = xplane.reduce(FIXTURE)
    assert sum(b - a for a, b in sp.busy) * 1e-9 == pytest.approx(red.busy_s, rel=1e-6)


def test_idle_inside_spans_against_the_sleeps(sp):
    setup, tune = one(sp, "walk/setup"), one(sp, "walk/tune")
    step, sync = one(sp, "serve/step"), one(sp, "serve/sync")
    # nothing ran on the chip in walk/setup: all of its 20 ms sleep is idle
    assert sp.busy_ns(setup.start, setup.end) == 0
    assert 20 * MS <= sp.idle_ns(setup) < 22 * MS
    # walk/tune: one fixture_step (about 0.1 ms) waited on, then 20 ms
    assert 0.05 * MS < sp.busy_ns(tune.start, tune.end) < 0.2 * MS
    assert 20 * MS <= sp.idle_ns(tune) < 22 * MS
    # serve/step: the step runs during the 10 ms sleep outside the sync,
    # the sync's 20 ms sleep is idle
    assert 20 * MS <= sp.idle_ns(sync) < 22 * MS
    outside = sp.idle_ns(step) - sp.idle_ns(sync)
    assert 9.8 * MS <= outside < 12 * MS
    # a child's idle is subtracted from its parent's
    assert sp.idle_s(("serve/step",), less=("serve/sync",)) == pytest.approx(outside * 1e-9)
    assert sp.idle_s(("serve/step",)) == pytest.approx(sp.idle_ns(step) * 1e-9)


def test_builds_are_counted_per_span(sp):
    student = one(sp, "walk/student")
    built = [e for e in sp.builds if e[0] == spans.LOWER_EVENT]
    assert built and all(student.start <= e[2] <= student.end for e in built)
    assert sp.builds_in(("walk/student",)) == len(built)
    assert sp.builds_in(("ebft/walk",)) == len(built)
    assert sp.builds_in(("walk/tune",)) == 0
    assert sp.builds_in(("serve/step",)) == 0


def test_readers_on_the_recorded_trace(sp):
    got = {m: reader(m).read(run_view(sp, blocks_tuned=1)) for m in METRICS}
    assert got["walk.tune_idle_ms_per_block"] == pytest.approx(sp.idle_ns(one(sp, "walk/tune")) / MS)
    stream = sum(sp.idle_ns(one(sp, n)) for n in ("walk/setup", "walk/student")) / MS
    assert got["walk.stream_idle_ms_per_block"] == pytest.approx(stream)
    assert got["walk.builds_per_block"] == sp.builds_in(("ebft/walk",))
    sync = sp.idle_ns(one(sp, "serve/sync")) / MS
    assert got["serve.sync_idle_ms_per_step"] == pytest.approx(sync)
    assert got["serve.dispatch_idle_ms_per_step"] == pytest.approx(
        sp.idle_ns(one(sp, "serve/step")) / MS - sync)


def test_readers_report_nothing_without_program_spans():
    """A program without the annotations (the older fixture) gives no
    reading and no error."""
    red = spans.reduce(os.path.join(DATA, "fixture.xplane.pb"))
    assert red.spans == []
    for m in METRICS:
        assert reader(m).read(run_view(red, blocks_tuned=4)) is None
        assert reader(m).read(run_view(None, blocks_tuned=4)) is None


def test_of_finds_the_traced_run_in_the_calling_frame(tmp_path):
    """``spans.of`` reads the trace of the run_cell frame that holds the
    view (its ``trace_dir``, ``mon`` and ``wall0``), once."""
    trace_dir = str(tmp_path)
    os.makedirs(os.path.join(trace_dir, "plugins", "profile", "run"))
    shutil.copy(FIXTURE, os.path.join(trace_dir, "plugins", "profile", "run",
                                      "host.xplane.pb"))
    evs, wall0 = events()
    mon = types.SimpleNamespace(events=evs)
    view = types.SimpleNamespace(counts={"blocks_tuned": 1})
    got = spans.of(view)
    assert [s.name for s in got.spans][:2] == ["ebft/walk", "walk/setup"]
    assert got.builds_in(("ebft/walk",)) == 1
    assert spans.of(view) is got and view.spans is got
    assert spans.of(types.SimpleNamespace()) is None
    del trace_dir, mon, wall0
