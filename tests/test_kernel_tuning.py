"""Kernel autotuner: candidate generation, the measured search, the
persistent cache (modes, staleness, corruption), wrapper integration,
the tuning_cache analysis pass, and the warm-run artifact gate."""
from __future__ import annotations

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import tuning
from repro.kernels.validation import VMEM_BUDGET_BYTES

F32 = "float32"
MM_DTYPES = {"x": F32, "w": F32}


@pytest.fixture(autouse=True)
def fresh_tuner(tmp_path):
    """Every test gets a clean tuner pointed at its own cache file."""
    tuning._reset_for_tests()
    tuning.configure(path=str(tmp_path / "cache.json"))
    yield
    tuning._reset_for_tests()


def _cache_path() -> str:
    return tuning.state()["path"]


def _write_cache(entries, schema=tuning.SCHEMA) -> str:
    path = _cache_path()
    with open(path, "w") as f:
        json.dump({"schema": schema, "code_rev": tuning.code_rev(),
                   "entries": entries}, f)
    return path


def _entry(dims=None, tiles=None, **over):
    base = {
        "kernel": "masked_matmul",
        "dims": dims or {"M": 64, "K": 128, "N": 128},
        "dtypes": dict(MM_DTYPES),
        "params": {},
        "backend": "cpu",
        "device_kind": "cpu",
        "code_rev": tuning.code_rev(),
        "tiles": tiles if tiles is not None else {"bm": 64, "bk": 128,
                                                  "bn": 128},
        "measured_s": {"default": 1.0, "best": 1.0},
        "candidates": 1,
    }
    base.update(over)
    return base


# ---------------------------------------------------------------------------
# candidate generation
# ---------------------------------------------------------------------------
def test_candidates_default_first_unique_and_valid():
    dims = {"M": 64, "K": 256, "N": 128}
    cands = tuning.candidate_tiles("masked_matmul", dims, MM_DTYPES)
    assert cands, "at least the default plan must be admitted"
    # candidate 0 is the (clamped) default plan
    default = tuning.build_plan("masked_matmul", dims, MM_DTYPES, {}, {})
    assert cands[0] == default.tiles
    seen = set()
    for tiles in cands:
        plan = tuning.build_plan("masked_matmul", dims, MM_DTYPES, {}, tiles)
        assert plan.vmem_bytes() <= VMEM_BUDGET_BYTES
        key = tuple(sorted(plan.tiles.items()))
        assert key not in seen, "clamp-duplicates must collapse"
        seen.add(key)


def test_candidates_respect_interpret_grid_cap():
    dims = {"M": 2048, "K": 2048, "N": 2048}
    cands = tuning.candidate_tiles("masked_matmul", dims, MM_DTYPES,
                                   interpret=True)
    for tiles in cands:
        plan = tuning.build_plan("masked_matmul", dims, MM_DTYPES, {}, tiles)
        assert int(np.prod(plan.grid)) <= tuning.INTERPRET_GRID_CAP


def test_candidates_respect_nm_group_alignment():
    dims = {"M": 32, "K": 256, "N": 128}
    params = {"n": 2, "m": 4}
    cands = tuning.candidate_tiles("nm_spmm", dims, {"x": F32, "v": F32},
                                   params)
    assert cands
    assert all(t["bk"] % 4 == 0 for t in cands)


def test_build_plan_rejects_unknown_kernel_and_knobs():
    with pytest.raises(ValueError, match="unknown kernel"):
        tuning.build_plan("conv", {}, {}, {}, {})
    with pytest.raises(ValueError, match="unknown tile knobs"):
        tuning.build_plan("masked_matmul", {"M": 8, "K": 128, "N": 128},
                          MM_DTYPES, {}, {"bz": 32})


# ---------------------------------------------------------------------------
# cache key
# ---------------------------------------------------------------------------
def test_cache_key_is_insertion_order_insensitive():
    a = tuning.cache_key("mm", {"M": 1, "K": 2}, {"x": F32}, {"p": 3},
                         "cpu", "cpu", "rev")
    b = tuning.cache_key("mm", {"K": 2, "M": 1}, {"x": F32}, {"p": 3},
                         "cpu", "cpu", "rev")
    assert a == b
    assert a != tuning.cache_key("mm", {"M": 1, "K": 2}, {"x": F32},
                                 {"p": 3}, "cpu", "cpu", "other-rev")


def test_code_rev_is_stable_within_a_process():
    assert tuning.code_rev() == tuning.code_rev()
    assert len(tuning.code_rev()) == 12


# ---------------------------------------------------------------------------
# measured search
# ---------------------------------------------------------------------------
def test_search_measures_default_inside_the_sweep():
    entry = tuning.search("masked_matmul", {"M": 16, "K": 128, "N": 128},
                          MM_DTYPES, interpret=True, reps=1,
                          max_candidates=3)
    ms = entry["measured_s"]
    # the acceptance ordering holds by construction, never by luck
    assert ms["best"] <= ms["default"]
    assert entry["code_rev"] == tuning.code_rev()
    assert entry["candidates"] >= 1
    tuning.build_plan(entry["kernel"], entry["dims"], entry["dtypes"],
                      entry["params"], entry["tiles"])  # winner is valid


def test_search_runs_all_three_kernels():
    for kernel, dims, dtypes, params in [
        ("nm_spmm", {"M": 8, "K": 128, "N": 128}, {"x": F32, "v": F32},
         {"n": 2, "m": 4}),
        ("flash_attention", {"BH": 2, "Sq": 64, "Sk": 64, "d": 64},
         {"q": F32}, {"causal": True}),
    ]:
        entry = tuning.search(kernel, dims, dtypes, params,
                              interpret=True, reps=1, max_candidates=2)
        assert entry["measured_s"]["best"] <= entry["measured_s"]["default"]


# ---------------------------------------------------------------------------
# resolution modes + persistence
# ---------------------------------------------------------------------------
def test_mode_off_returns_defaults_and_counts_nothing():
    tiles, source = tuning.resolve("masked_matmul",
                                   {"M": 16, "K": 128, "N": 128}, MM_DTYPES)
    assert (tiles, source) == ({}, None)
    assert tuning.stats() == {"hits": 0, "misses": 0, "searches": 0,
                              "search_s": 0.0}


def test_mode_cache_miss_is_free_and_writes_nothing():
    tuning.configure(mode="cache")
    tiles, source = tuning.resolve("masked_matmul",
                                   {"M": 16, "K": 128, "N": 128}, MM_DTYPES)
    assert (tiles, source) == ({}, "default")
    assert tuning.stats()["misses"] == 1
    assert not os.path.exists(_cache_path())


def test_mode_search_persists_and_later_processes_hit():
    tuning.configure(mode="search")
    dims = {"M": 16, "K": 128, "N": 128}
    tiles, source = tuning.resolve("masked_matmul", dims, MM_DTYPES,
                                   interpret=True)
    assert source == "search"
    assert tuning.stats()["searches"] == 1
    assert tuning.stats()["search_s"] > 0

    with open(_cache_path()) as f:
        payload = json.load(f)
    assert payload["schema"] == tuning.SCHEMA
    assert len(payload["entries"]) == 1

    # a fresh process (state reset, same path) in cache mode hits
    path = _cache_path()
    tuning._reset_for_tests(mode="cache")
    tuning.configure(path=path)
    tiles2, source2 = tuning.resolve("masked_matmul", dims, MM_DTYPES,
                                     interpret=True)
    assert source2 == "cache" and tiles2 == tiles
    assert tuning.stats() == {"hits": 1, "misses": 0, "searches": 0,
                              "search_s": 0.0}


def test_corrupt_cached_tiles_degrade_to_a_miss():
    tuning.configure(mode="search")
    dims = {"M": 16, "K": 128, "N": 128}
    tuning.resolve("masked_matmul", dims, MM_DTYPES, interpret=True)

    path = _cache_path()
    with open(path) as f:
        payload = json.load(f)
    for entry in payload["entries"].values():
        entry["tiles"] = {"bm": 7, "bk": 128, "bn": 128}  # 16 % 7 != 0
    with open(path, "w") as f:
        json.dump(payload, f)

    tuning._reset_for_tests(mode="cache")
    tuning.configure(path=path)
    tiles, source = tuning.resolve("masked_matmul", dims, MM_DTYPES,
                                   interpret=True)
    assert (tiles, source) == ({}, "default")  # no crash, defaults run
    assert tuning.stats()["misses"] == 1


def test_unknown_schema_or_garbage_file_starts_fresh():
    tuning.configure(mode="cache")
    path = _cache_path()  # capture before any reset (reset restores default)
    _write_cache({"k": _entry()}, schema="repro.kernels.tuning/v999")
    _, source = tuning.resolve("masked_matmul",
                               {"M": 64, "K": 128, "N": 128}, MM_DTYPES)
    assert source == "default"

    tuning._reset_for_tests(mode="cache")
    tuning.configure(path=path)
    with open(path, "w") as f:
        f.write("{not json")
    _, source = tuning.resolve("masked_matmul",
                               {"M": 64, "K": 128, "N": 128}, MM_DTYPES)
    assert source == "default"


def test_store_round_trips_through_load():
    entry = _entry()
    key = tuning.store(entry)
    path = _cache_path()
    tuning._reset_for_tests(mode="cache")
    tuning.configure(path=path)
    tuning._load()
    assert key in tuning._STATE.cache
    # and no stray .tmp files left behind (atomic rename)
    assert [f for f in os.listdir(os.path.dirname(path))
            if f.endswith(".tmp")] == []


# ---------------------------------------------------------------------------
# wrapper integration: the kernel path consults the tuner
# ---------------------------------------------------------------------------
def test_wrapper_searches_then_hits_and_stays_correct():
    from repro.kernels.masked_matmul import ops as MM
    from repro.kernels.masked_matmul.ref import masked_matmul_ref

    tuning.configure(mode="search")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    mask = jnp.asarray(rng.random((128, 128)) > 0.5)

    out = MM.masked_matmul(x, w, mask, interpret=True)
    assert tuning.stats()["searches"] == 1
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(masked_matmul_ref(x, w, mask)),
                               rtol=2e-5, atol=2e-5)

    MM.masked_matmul(x, w, mask, interpret=True)  # same shape: cache hit
    assert tuning.stats()["hits"] == 1

    # explicit tiles bypass the tuner entirely
    before = tuning.stats()
    MM.masked_matmul(x, w, mask, interpret=True, bm=16, bk=64, bn=64)
    assert tuning.stats() == before


# ---------------------------------------------------------------------------
# launcher pre-tuning workloads
# ---------------------------------------------------------------------------
def test_ebft_workloads_cover_the_walk_kernels():
    from repro.configs import get_config

    cfg = get_config("tiny_dense")
    work = tuning.ebft_workloads(cfg, tokens=256, seq=32, pattern=(2, 4))
    kinds = {w[0] for w in work}
    assert {"masked_matmul", "flash_attention"} <= kinds
    assert "nm_spmm" in kinds  # tiny_dense dims are 4-aligned
    for kernel, dims, dtypes, params in work:
        assert all(v > 0 for v in dims.values())
        tuning.build_plan(kernel, dims, dtypes, params, {})  # plannable

    # pretune with tuning off resolves every workload to the defaults
    records = tuning.pretune(work, interpret=True)
    assert len(records) == len(work)
    assert all(r["source"] is None and r["tiles"] == {} for r in records)


# ---------------------------------------------------------------------------
# the tuning_cache analysis pass
# ---------------------------------------------------------------------------
def _codes(findings):
    return [f.code for f in findings]


def test_analysis_missing_file_is_clean():
    from repro.analysis.tuning_cache import check_cache

    assert check_cache(_cache_path()) == []


def test_analysis_accepts_a_freshly_searched_cache():
    from repro.analysis.tuning_cache import check_cache

    tuning.configure(mode="search")
    tuning.resolve("masked_matmul", {"M": 16, "K": 128, "N": 128},
                   MM_DTYPES, interpret=True)
    assert check_cache(_cache_path()) == []


def test_analysis_flags_invalid_tiles_as_tun001():
    from repro.analysis.tuning_cache import check_cache

    _write_cache({"k": _entry(tiles={"bm": 7, "bk": 128, "bn": 128})})
    findings = check_cache(_cache_path())
    assert _codes(findings) == ["TUN001"]
    assert findings[0].severity == "error"


def test_analysis_flags_vmem_blowout_as_tun002():
    from repro.analysis.tuning_cache import check_cache

    # valid grid, but 2048^2 f32 tiles: far past the 16 MiB budget —
    # the search can never emit this, so it must be a doctored entry
    entry = _entry(dims={"M": 4096, "K": 4096, "N": 4096},
                   tiles={"bm": 2048, "bk": 2048, "bn": 2048})
    _write_cache({"k": entry})
    assert _codes(check_cache(_cache_path())) == ["TUN002"]


def test_analysis_flags_stale_code_rev_as_tun003_warn():
    from repro.analysis.tuning_cache import check_cache

    _write_cache({"k": _entry(code_rev="000000000000")})
    findings = check_cache(_cache_path())
    assert _codes(findings) == ["TUN003"]
    assert findings[0].severity == "warn"


def test_analysis_flags_malformed_entries_as_tun004():
    from repro.analysis.tuning_cache import check_cache

    entry = _entry()
    del entry["tiles"]
    _write_cache({"a": entry, "b": "not-an-object"})
    assert sorted(_codes(check_cache(_cache_path()))) == ["TUN004", "TUN004"]

    with open(_cache_path(), "w") as f:
        f.write("[]")
    assert _codes(check_cache(_cache_path())) == ["TUN004"]


def test_analysis_pass_registered_in_orchestrator():
    from repro.analysis import PASS_NAMES, run

    assert "tuning_cache" in PASS_NAMES
    _write_cache({"k": _entry(tiles={"bm": 7, "bk": 128, "bn": 128})})
    report = run(config_names=["tiny_dense"], passes=["tuning_cache"],
                 tuning_cache_path=_cache_path())
    assert report.exit_code("error") == 1
    assert [f.code for f in report.findings] == ["TUN001"]


# ---------------------------------------------------------------------------
# the warm-run artifact gate (obs validate --require-cache-hits)
# ---------------------------------------------------------------------------
def _payload(tuning_section):
    out = {
        "manifest": {"schema": "repro.obs/v1", "name": "t",
                     "created_unix": 0.0, "argv": [],
                     "platform": "cpu", "device_kind": "cpu",
                     "device_count": 1},
        "metrics": {},
        "trace": [],
    }
    if tuning_section is not None:
        out["kernel_tuning"] = tuning_section
    return out


def test_require_cache_hits_passes_on_a_warm_run():
    from repro.obs.run import validate_payload

    warm = {"mode": "cache", "hits": 5, "misses": 0, "searches": 0,
            "search_s": 0.0}
    assert validate_payload(_payload(warm), require_cache_hits=True) == []


@pytest.mark.parametrize("section,needle", [
    (None, "kernel_tuning"),
    ({"hits": 0, "misses": 0, "searches": 0, "search_s": 0.0}, "hits"),
    ({"hits": 3, "misses": 2, "searches": 0, "search_s": 0.0}, "misses"),
    ({"hits": 3, "misses": 0, "searches": 1, "search_s": 0.4}, "searches"),
])
def test_require_cache_hits_rejects_cold_or_missing(section, needle):
    from repro.obs.run import validate_payload

    problems = validate_payload(_payload(section), require_cache_hits=True)
    assert problems and any(needle in p for p in problems)
    # and without the gate the same artifact is fine
    assert validate_payload(_payload(section)) == []
