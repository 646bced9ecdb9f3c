"""A new configuration, traffic mix, cell and per-layer metric are new
files and new BENCHMARK.json entries: the harness resolves them by name,
and no existing file of the benchmark changes. Also checks the committed
BENCHMARK.json against the files it names."""
import hashlib
import json
import os

import bench_tiny as T

from harness import spec as S


def digests(bench_dir):
    out = {}
    for d, _, files in os.walk(bench_dir):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, bench_dir)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_files_resolve_by_name(tmp_path):
    root = T.make_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    before = digests(bench)
    with open(os.path.join(bench, "configs", "throwaway.json"), "w") as f:
        json.dump({**T.TINY_QWEN, "name": "throwaway"}, f)
    with open(os.path.join(bench, "traffic", "throwaway_mix.json"), "w") as f:
        json.dump(T.TINY_WALK, f)
    with open(os.path.join(bench, "limits", "throwaway-cell.json"), "w") as f:
        json.dump({"limits": {"loss_before": 0.5}}, f)
    with open(os.path.join(bench, "metrics", "throwaway.answer.py"), "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "throwaway", "source": "test",
                            "file": "bench/configs/throwaway.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "throwaway-cell", "config": "throwaway",
                              "traffic": "throwaway_mix", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "throwaway.answer", "unit": "%", "better": "higher",
                              "source": "device_trace", "layer": "device",
                              "moves": "walk_block_s", "workloads": ["throwaway-cell"]})
    for m in spec["end_to_end"]:
        if m["name"] == "walk_block_s":
            m["workloads"].append("throwaway-cell")
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    cell = S.cell(S.load_spec(root), "throwaway-cell", root)
    assert cell.config["name"] == "throwaway"
    assert cell.traffic == T.TINY_WALK
    assert cell.limits == {"loss_before": 0.5}
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "walk_block_s"}
    assert [m["name"] for m in cell.per_layer] == ["throwaway.answer"]
    assert cell.metric_reader("throwaway.answer").read(None) == 42.0
    assert cell.driver().Driver.__name__ == "Driver"
    assert cell.reference().PRUNABLE
    after = digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_tiny_cells_are_new_files_only(tmp_path):
    """The tiny cells, tiny_moe's with a reference of its own, go into the
    test root as new files and BENCHMARK.json entries: every file of the
    benchmark reads as committed."""
    root = T.make_root(str(tmp_path))
    committed = {k: v for k, v in digests(T.BENCH).items()
                 if not k.startswith("tests" + os.sep) and "__pycache__" not in k}
    copied = digests(os.path.join(root, "bench"))
    assert {k: copied.get(k) for k in committed} == committed
    spec = S.load_spec(root)
    for name in ("tiny-moe-walk", "tiny-moe-serve"):
        ref = S.cell(spec, name, root).reference()
        assert ref.__file__ == os.path.join(root, "bench", "configs", "ref_moe.py")
        assert ref.BLOCK_KEYS == ("dense_blocks", "moe_blocks")
    committed_spec = S.load_spec(T.ROOT)
    assert committed_spec["configs"] == spec["configs"][:len(committed_spec["configs"])]
    assert committed_spec["workloads"] == spec["workloads"][:len(committed_spec["workloads"])]


def test_committed_spec_resolves():
    spec = S.load_spec(T.ROOT)
    assert spec["command"] == ["python3", "bench/run.py"] and spec["paths"] == ["bench"]
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for w in spec["workloads"]:
        cell = S.cell(spec, w["name"], T.ROOT)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(cell.metric_reader(m["name"]).read)
        assert callable(cell.driver().Driver)
        assert cell.limits
    for c in spec["configs"]:
        conf = S.load_json(os.path.join(T.ROOT, c["file"]))
        assert conf["source"] == c["source"]
        for key in c["reduced"]:
            assert key in conf["published"] and conf[key] != conf["published"][key]
