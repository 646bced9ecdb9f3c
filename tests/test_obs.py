"""repro.obs: null-mode invariants, span nesting/timing, metric
instruments, sink round-trips through the report CLI, schema
validation, profiling hooks, and the instrumented-pipeline integration
test (tiny ebft_run -> valid BENCH_ebft.json)."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.obs.__main__ import main as obs_cli
from repro.obs.profile import ebft_live_block_bytes, is_abstract, profiled
from repro.obs.run import current_run, start_run, validate_payload
from repro.obs.sinks import load_artifact, read_jsonl


@pytest.fixture(autouse=True)
def _obs_reset():
    """Every test starts and ends with the null singletons installed."""
    OT.set_tracer(None)
    OM.set_registry(None)
    yield
    run = current_run()
    if run is not None:
        run.finish()
    OT.set_tracer(None)
    OM.set_registry(None)


# ---------------------------------------------------------------------------
# null mode: disabled observability produces zero events
# ---------------------------------------------------------------------------
def test_null_mode_no_events_no_state():
    assert not OT.enabled() and not OM.enabled()
    with OT.span("outer", a=1) as sp:
        with OT.span("inner") as inner:
            # the profiler annotation alone: no clock, no tree, no slots
            assert isinstance(inner, jax.profiler.TraceAnnotation)
            assert type(inner) is type(sp) and not type(sp).__slots__
        sp.set(b=2)
        assert sp.fence(42) == 42  # fence is identity when off
    OM.counter("c").inc(5)
    OM.gauge("g").set(3.0)
    OM.histogram("h").observe(1.0)
    OM.series("s").append(1.0, step=0)
    assert OT.get_tracer().tree() == []
    assert OM.summary() == {}
    assert sp.duration == 0.0 and sp.attrs == {}


# ---------------------------------------------------------------------------
# spans: nesting + timing monotonicity
# ---------------------------------------------------------------------------
def test_span_nesting_and_timing_monotonicity():
    run = start_run("t", console=False)
    with OT.span("walk", epochs=2) as w:
        with OT.span("block", index=0):
            pass
        with OT.span("block", index=1) as b1:
            with OT.span("step"):
                pass
            b1.set(loss=0.5)

    forest = run.tracer.tree()
    assert [r["name"] for r in forest] == ["walk"]
    blocks = forest[0]["children"]
    assert [b["name"] for b in blocks] == ["block", "block"]
    assert blocks[1]["children"][0]["name"] == "step"
    assert blocks[1]["attrs"] == {"index": 1, "loss": 0.5}

    # monotonicity: children start no earlier than the parent, end no
    # later, and sibling starts are ordered
    assert w.duration >= b1.duration >= b1.children[0].duration >= 0.0
    assert blocks[0]["start"] >= forest[0]["start"]
    assert blocks[1]["start"] >= blocks[0]["start"] + blocks[0]["duration_s"]
    for node in (forest[0], blocks[0], blocks[1]):
        assert node["duration_s"] >= sum(
            c["duration_s"] for c in node.get("children", [])
        )

    run.finish()
    assert not OT.enabled()  # finish restores the null singletons


def test_span_stack_unwinds_on_exception():
    run = start_run("t", console=False)
    with pytest.raises(RuntimeError):
        with OT.span("outer"):
            with OT.span("inner"):
                raise RuntimeError("boom")
    # both spans closed despite the exception; a new span is a root
    with OT.span("after"):
        pass
    assert [r["name"] for r in run.tracer.tree()] == ["outer", "after"]


# ---------------------------------------------------------------------------
# metrics instruments
# ---------------------------------------------------------------------------
def test_metric_instruments_and_summaries():
    start_run("t", console=False)
    OM.counter("tokens").inc(3)
    OM.counter("tokens").inc(2)
    g = OM.gauge("live_bytes")
    for v in (10.0, 50.0, 20.0):
        g.set(v)
    h = OM.histogram("lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    s = OM.series("loss")
    s.append(2.0, step=0)
    s.append(1.0, step=10)

    out = OM.summary()
    assert out["tokens"] == {"kind": "counter", "value": 5.0}
    assert out["live_bytes"]["last"] == 20.0
    assert out["live_bytes"]["max"] == 50.0  # peak survives the summary
    assert out["live_bytes"]["min"] == 10.0
    assert out["lat"]["count"] == 4 and out["lat"]["mean"] == 2.5
    assert out["lat"]["min"] == 1.0 and out["lat"]["max"] == 4.0
    assert out["loss"]["first"] == 2.0 and out["loss"]["last"] == 1.0
    assert out["loss"]["points"] == [[0.0, 2.0], [10.0, 1.0]]

    with pytest.raises(TypeError):  # kind mismatch is a bug, not a merge
        OM.gauge("tokens")


# ---------------------------------------------------------------------------
# sinks: JSONL round-trip through the report CLI
# ---------------------------------------------------------------------------
def test_jsonl_roundtrip_and_report_cli(tmp_path, capsys):
    jsonl = tmp_path / "events.jsonl"
    summary = tmp_path / "BENCH_t.json"
    run = start_run("roundtrip", config="tiny_dense", method="wanda",
                    sparsity=0.5, console=False, jsonl_path=str(jsonl))
    with OT.span("phase/work", what="stuff"):
        OM.counter("work/items").inc(7)
    run.finish(extra={"answer": 42}, summary_path=str(summary))

    events = read_jsonl(str(jsonl))
    assert events[0]["type"] == "manifest"
    assert events[0]["manifest"]["name"] == "roundtrip"
    kinds = {e["type"] for e in events[1:]}
    assert {"counter", "span"} <= kinds
    span_ev = next(e for e in events if e["type"] == "span")
    assert span_ev["name"] == "phase/work" and span_ev["duration_s"] >= 0

    # the report CLI renders both artifact formats
    for artifact in (str(summary), str(jsonl)):
        assert obs_cli(["report", artifact]) == 0
        out = capsys.readouterr().out
        assert "roundtrip" in out and "phase/work" in out
    assert obs_cli(["report", str(summary), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["answer"] == 42
    assert payload["metrics"]["work/items"]["value"] == 7.0

    # validate: summary passes (with required keys), raw JSONL is not a
    # summary artifact and must fail
    assert obs_cli(["validate", str(summary), "--require", "answer"]) == 0
    capsys.readouterr()
    assert obs_cli(["validate", str(jsonl)]) == 1
    capsys.readouterr()
    assert obs_cli(["report", str(tmp_path / "missing.json")]) == 2


def test_validate_payload_rejects_malformed():
    run = start_run("ok", console=False)
    payload = run.finish()
    assert validate_payload(payload) == []
    assert validate_payload(payload, require=["blocks"]) \
        == ["missing required key 'blocks'"]

    bad = dict(payload, manifest=dict(payload["manifest"], schema="nope/v9"))
    assert any("schema" in p for p in validate_payload(bad))
    assert validate_payload({"metrics": {}, "trace": []}) \
        == ["missing 'manifest' object"]
    assert validate_payload([1, 2]) \
        == ["artifact is list, expected object"]


def test_validate_payload_dispatch_budget():
    run = start_run("ok", console=False)
    payload = run.finish()
    # no dispatch object at all
    assert any("dispatch" in p for p in
               validate_payload(payload, max_dispatches_per_block=4))
    within = dict(payload, dispatch={"per_block_max": 3})
    assert validate_payload(within, max_dispatches_per_block=4) == []
    over = dict(payload, dispatch={"per_block_max": 9})
    probs = validate_payload(over, max_dispatches_per_block=4)
    assert probs and "exceeds budget 4" in probs[0]
    # malformed field type
    bad = dict(payload, dispatch={"per_block_max": "lots"})
    assert any("non-integer" in p for p in
               validate_payload(bad, max_dispatches_per_block=4))
    # no budget requested -> no dispatch requirements
    assert validate_payload(payload) == []


# ---------------------------------------------------------------------------
# profiling
# ---------------------------------------------------------------------------
def test_profiled_fn_splits_compile_from_execution():
    start_run("t", console=False)
    f = profiled(jax.jit(lambda x: x * 2.0 + 1.0), "test/step")
    x = jnp.arange(8.0)
    for _ in range(3):
        out = f(x)
    assert out[1] == 3.0
    s = OM.summary()
    assert s["test/step/compiles"]["value"] == 1.0  # one signature
    assert s["test/step/exec_s"]["count"] == 3
    assert s["test/step/lower_s"]["last"] >= 0.0
    assert s["test/step/compile_s"]["last"] > 0.0
    # a second shape triggers exactly one more compile
    f(jnp.arange(4.0))
    assert OM.summary()["test/step/compiles"]["value"] == 2.0


def test_profiled_fn_passthrough_when_disabled_or_traced():
    f = profiled(jax.jit(lambda x: x + 1.0), "test/off")
    assert float(f(jnp.float32(1.0))) == 2.0  # obs off: raw call
    start_run("t", console=False)
    # under an outer trace the wrapper must not lower/fence tracers
    outer = jax.jit(lambda x: f(x) * 2.0)
    assert float(outer(jnp.float32(1.0))) == 4.0
    s = OM.summary()
    assert "test/off/exec_s" not in s and "test/off/compiles" not in s


def test_build_events_book_onto_innermost_span():
    """The run's monitoring listener books JAX's program-build events
    onto the innermost open span; a warm call books none."""
    start_run("t", console=False)
    f = jax.jit(lambda x: x * 3.0 - 1.0)
    x = jnp.arange(4.0)
    with OT.span("outer") as outer:
        with OT.span("inner") as inner:
            f(x).block_until_ready()
    assert inner.attrs["builds"] == 1 and inner.attrs["build_s"] > 0.0
    assert "build_s" not in outer.attrs and "builds" not in outer.attrs
    with OT.span("again") as again:
        f(x).block_until_ready()
    assert again.attrs == {}
    # the listener leaves with the run
    run = current_run()
    run.finish()
    import jax._src.monitoring as monitoring

    assert run.tracer.book_build not in \
        monitoring._event_duration_secs_listeners


def test_totals_sums_spans_and_subtree_builds():
    forest = [{"name": "w", "duration_s": 9.0, "children": [
        {"name": "walk/tune", "duration_s": 2.0, "attrs": {"build_s": 0.5},
         "children": [{"name": "ebft/block", "duration_s": 1.5,
                       "attrs": {"build_s": 1.0}}]},
        {"name": "walk/tune", "duration_s": 1.0},
        {"name": "walk/student", "duration_s": 0.25}]}]
    assert OT.totals(forest, "walk/tune") == (3.0, 1.5)
    assert OT.totals(forest, "walk/student") == (0.25, 0.0)
    assert OT.totals(forest, "walk/teacher") == (0.0, 0.0)


# ---------------------------------------------------------------------------
# spans on the profiler clock
# ---------------------------------------------------------------------------
def _host_events(trace_dir):
    """(name, start_ns, end_ns, stats) of every host event of the trace."""
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


@pytest.mark.parametrize("live", [False, True], ids=["null", "live"])
def test_span_is_a_profiler_host_event(tmp_path, live):
    if live:
        start_run("t", console=False)
    f = jax.jit(lambda x: x + 1.0)
    f(jnp.ones(3)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with OT.span("walk/tune", block=3):
            with OT.span("serve/sync"):
                f(jnp.ones(3)).block_until_ready()
    events = _host_events(tmp_path)
    (_, t0, t1, stats), = [e for e in events if e[0] == "walk/tune"]
    assert stats == {"block": 3}
    (_, s0, s1, _), = [e for e in events if e[0] == "serve/sync"]
    assert t0 <= s0 <= s1 <= t1
    assert OT.enabled() == live and bool(OT.get_tracer().tree()) == live


def _tiny(arch="tiny_dense"):
    from repro.configs import get_config
    from repro.models.model import build

    model = build(get_config(arch))
    return model, model.init(jax.random.PRNGKey(0))


def _names(nodes):
    return [(n["name"], n.get("attrs", {}).get("block")) for n in nodes]


def test_finetune_records_walk_phase_spans():
    """One finetune: walk/setup before the first block, then teacher,
    tune and student once for each block, in that order."""
    import numpy as np

    from repro.core import ebft
    from repro.core.masks import prune

    model, params = _tiny()
    calib = np.random.default_rng(0).integers(
        0, model.cfg.vocab_size, size=(16, 32)).astype(np.int32)
    masks, pruned = prune(model, params, calib, method="magnitude",
                          sparsity=0.5)
    run = start_run("t", console=False)
    _, reports = ebft.finetune(model, params, pruned, masks, calib,
                               ebft.EBFTConfig(epochs=1, microbatch=8))
    walk, = run.tracer.tree()
    assert walk["name"] == "ebft/walk"
    names = _names(walk["children"])
    first = names.index(("walk/teacher", 0))
    assert first > 0 and all(n == "walk/setup" for n, _ in names[:first])
    blocks = [r.index for r in reports]
    assert blocks == list(range(model.num_blocks))
    assert [n for n in names[first:] if n[0] != "walk/setup"] == [
        (f"walk/{phase}", i) for i in blocks
        for phase in ("teacher", "tune", "student")]
    # the tune step and the per-block stream advances (the teacher's
    # dispatch builds the program the student's reuses) were built inside
    # the phases that called them
    assert OT.totals([walk], "walk/tune")[1] > 0
    assert OT.totals([walk], "walk/teacher")[1] > 0


def test_serve_records_admit_step_and_sync_spans():
    import numpy as np

    from repro.serving.decode import Request, Server

    model, params = _tiny()
    rng = np.random.default_rng(3)
    reqs = [Request(uid=u, prompt=rng.integers(0, 500, size=(8,)).astype(np.int32),
                    max_new=n) for u, n in ((7, 4), (8, 3))]
    run = start_run("t", console=False)
    Server(model, params, batch_size=1, max_len=32).serve(reqs)
    batch, = run.tracer.tree()
    kids = batch["children"]
    admits = [k for k in kids if k["name"] == "serve/admit"]
    steps = [k for k in kids if k["name"] == "serve/step"]
    assert [a["attrs"]["uid"] for a in admits] == [7, 8]
    # one slot: the first id comes with the prefill, one step per id after
    assert len(steps) == (4 - 1) + (3 - 1)
    assert {k["name"] for k in kids} == {"serve/admit", "serve/step"}
    for st in steps:
        assert [c["name"] for c in st["children"]] == ["serve/sync"]



def test_serve_dispatches_ahead_but_never_past_a_last_step():
    """One slot, max_new 4 then 3: every step but a request's last
    dispatches the next step before its read, and the counter counts them."""
    import numpy as np

    from repro.serving.decode import Request, Server

    model, params = _tiny()
    rng = np.random.default_rng(3)
    reqs = [Request(uid=u, prompt=rng.integers(0, 500, size=(8,)).astype(np.int32),
                    max_new=n) for u, n in ((7, 4), (8, 3))]
    run = start_run("t", console=False)
    Server(model, params, batch_size=1, max_len=32).serve(reqs)
    batch, = run.tracer.tree()
    steps = [k for k in batch["children"] if k["name"] == "serve/step"]
    assert [st["attrs"]["ahead"] for st in steps] == [1, 1, 0, 1, 0]
    assert OM.summary()["serve/steps_ahead"]["value"] == 3

def test_is_abstract_and_live_bytes():
    assert not is_abstract(jnp.ones(3), {"a": 1.0})
    seen = []
    jax.jit(lambda x: seen.append(is_abstract(x)) or x)(jnp.ones(2))
    assert seen == [True]
    block = {"w": jnp.ones((4, 4), jnp.float32)}
    masks = {"w": jnp.ones((4, 4), jnp.float32)}
    # 16 weights f32 + 16 mask f32 + 2 moments * 16 * 4B
    assert ebft_live_block_bytes(block, masks) == 64 + 64 + 128


# ---------------------------------------------------------------------------
# integration: the instrumented pipeline emits a valid BENCH_ebft.json
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_ebft_run_emits_valid_bench_artifact(tmp_path, capsys):
    from repro.launch.ebft_run import main as ebft_main

    bench = tmp_path / "BENCH_ebft.json"
    jsonl = tmp_path / "events.jsonl"
    ebft_main(["--arch", "tiny_dense", "--pretrain-steps", "30",
               "--batch", "8", "--seq", "32", "--calib-samples", "8",
               "--epochs", "2", "--bench-out", str(bench),
               "--obs-jsonl", str(jsonl)])
    console = capsys.readouterr().out
    assert "EBFT ppl" in console  # console sink preserved

    payload = load_artifact(str(bench))
    assert validate_payload(
        payload,
        require=["blocks", "phases", "perplexity", "ebft", "dispatch",
                 "walk_phases"],
        max_dispatches_per_block=4,  # epochs (2) + 2, the CI budget
    ) == []
    assert payload["manifest"]["config"] == "tiny_dense"
    assert payload["manifest"]["method"] == "wanda"

    # per-block reconstruction data survived the launcher (the BlockReport
    # plumbing bug this layer fixed)
    blocks = payload["blocks"]
    assert blocks and len(blocks) == payload["ebft"]["num_blocks"]
    for b in blocks:
        assert b["epochs_run"] >= 1
        assert b["loss_after"] <= b["loss_before"]
        assert b["early_stop"] in ("plateau", "max_epochs")
        # history = [E_before] + one entry per epoch run
        assert len(b["history"]) == b["epochs_run"] + 1
        assert b["live_bytes"] > 0
        assert b["path"] == "fused"
        assert b["dispatches"] == 1 and b["host_syncs"] == 1

    # the fused-walk accounting: per-block = 1 tune + 2 stream advances
    assert payload["ebft"]["fused_epochs"] is True
    assert payload["dispatch"]["per_block_max"] == 3
    assert payload["dispatch"]["fused_all_blocks"] is True
    # per-phase walk wall-clock was recorded, with first-call
    # (trace+compile) time split out of the steady-state sums
    for phase in ("teacher", "tune", "student"):
        assert payload["walk_phases"][phase] > 0
        assert payload["walk_phases"][f"{phase}_compile"] >= 0
    # the walk definitely compiled something (adv_scan per block index,
    # the fused tune step) and none of it may hide in the phase sums
    assert sum(payload["walk_phases"][f"{p}_compile"]
               for p in ("teacher", "tune", "student")) > 0

    # the tile-plan autotuner section is present (default mode: cache)
    kt = payload["kernel_tuning"]
    assert kt["mode"] == "cache"
    assert kt["searches"] == 0 and kt["search_s"] == 0.0
    assert kt["hits"] + kt["misses"] >= 1  # pretune resolved the workloads

    # phases + the paper's streaming-memory measurement
    assert {"pretrain", "prune", "ebft", "eval_dense"} <= set(payload["phases"])
    assert all(v >= 0 for v in payload["phases"].values())
    peak = payload["ebft"]["peak_live_block_bytes"]
    assert peak == max(b["live_bytes"] for b in blocks)
    assert payload["metrics"]["ebft/live_block_bytes"]["max"] == peak
    assert {"dense", "wanda", "EBFT"} <= set(payload["perplexity"])

    # trace forest contains the phase spans with nested ebft blocks
    names = {s["name"] for s in payload["trace"]}
    assert {"phase/pretrain", "phase/prune", "phase/ebft"} <= names
    ebft_phase = next(s for s in payload["trace"] if s["name"] == "phase/ebft")
    walk = ebft_phase["children"][0]
    assert walk["name"] == "ebft/walk"
    # the stacked walk wraps each visit in teacher/tune/student phase
    # spans; ebft/block nests inside walk/tune
    walk_names = [c["name"] for c in walk["children"]]
    assert {"walk/teacher", "walk/tune", "walk/student"} <= set(walk_names)
    tune_spans = [c for c in walk["children"] if c["name"] == "walk/tune"]
    assert len([g for t in tune_spans for g in t.get("children", [])
                if g["name"] == "ebft/block"]) == len(blocks)

    # event stream is crash-safe JSONL with the same manifest
    events = read_jsonl(str(jsonl))
    assert events[0]["manifest"]["name"] == "ebft_run"
    assert any(e.get("name") == "ebft/block" for e in events)

    # report CLI renders the artifact
    assert obs_cli(["report", str(bench)]) == 0
    out = capsys.readouterr().out
    assert "ebft/block" in out or "blocks" in out

    # run state was released
    assert current_run() is None and not OT.enabled()
