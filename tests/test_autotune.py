"""Shape-keyed autotuner: cache round-trip, hit short-circuit, corrupted
/stale fallback, seed-cache legality, resolution precedence, interpret
switch."""
import json

import pytest

from repro.core.formats import FMT_CIFAR, FMT_IMAGENET
from repro.kernels import runtime
from repro.kernels.autotune import (
    CACHE_SCHEMA_VERSION,
    SEED_CACHE_PATH,
    BlockConfig,
    TuneCache,
    TuneSpec,
    check_cache,
    default_block_config,
    gemm_candidates,
    registry_specs,
    resolve_block_config,
    tune,
    verify_config,
)

SPEC = TuneSpec("gemm", (64, 64, 64), FMT_CIFAR, k_block=32)
QSPEC = TuneSpec("quantize", (64, 64), FMT_CIFAR, k_block=32)


def _fake_timer(times=None, calls=None):
    """Timer stub: records calls, serves canned (or constant) timings."""
    def timer(spec, config):
        if calls is not None:
            calls.append((spec.key(), config))
        if times:
            return times.pop(0)
        return 100.0
    return timer


# ---------------------------------------------------------------------------
# cache round-trip
# ---------------------------------------------------------------------------
def test_cache_round_trip_identical_blockconfig(tmp_path):
    path = tmp_path / "tune.json"
    cache = TuneCache(path)
    cfg = BlockConfig(64, 32, 16, "c")
    cache.put(SPEC, cfg, 123.456, timed=7)
    cache.save()

    loaded = TuneCache.load(path)
    assert not loaded.load_warnings
    assert loaded.get(SPEC.key()) == cfg  # identical, not just equal fields
    ent = loaded.entries[SPEC.key()]
    assert ent["us"] == 123.46 and ent["candidates_timed"] == 7
    assert TuneSpec.from_json(ent) == SPEC


def test_blockconfig_json_round_trip():
    cfg = BlockConfig(128, 64, 256, "none")
    assert BlockConfig.from_json(cfg.to_json()) == cfg


# ---------------------------------------------------------------------------
# tuning: short-circuit and verifier pruning
# ---------------------------------------------------------------------------
def test_cache_hit_short_circuits_timing(tmp_path):
    cache = TuneCache(tmp_path / "tune.json")
    calls = []
    winner = tune(SPEC, cache, timer=_fake_timer(calls=calls))
    assert calls, "first tune must time candidates"
    n_first = len(calls)
    again = tune(SPEC, cache, timer=_fake_timer(calls=calls))
    assert again == winner
    assert len(calls) == n_first, "cache hit must not re-time"


def test_tune_times_only_verified_candidates(tmp_path):
    cache = TuneCache(tmp_path / "tune.json")
    calls = []
    tune(SPEC, cache, timer=_fake_timer(calls=calls))
    assert len(calls) == len(
        [c for c in gemm_candidates(SPEC) if verify_config(SPEC, c).ok])


def test_tune_persists_winner_by_min_time(tmp_path):
    cache = TuneCache(tmp_path / "tune.json")
    n = len(gemm_candidates(SPEC))
    times = [float(100 - i) for i in range(n)]  # last candidate fastest
    winner = tune(SPEC, cache, timer=_fake_timer(times=list(times)))
    assert cache.get(SPEC.key()) == winner
    legal = [c for c in gemm_candidates(SPEC) if verify_config(SPEC, c).ok]
    assert winner == legal[-1]


# ---------------------------------------------------------------------------
# corrupted / stale caches degrade to defaults, never crash
# ---------------------------------------------------------------------------
def test_corrupted_cache_falls_back_to_default(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text("{not json at all")
    cache = TuneCache.load(path)
    assert len(cache) == 0 and cache.load_warnings
    resolved = resolve_block_config(
        "gemm", SPEC.shape, SPEC.fmt, k_block=32, cache=cache)
    assert resolved == default_block_config(
        shape=SPEC.shape, fmt=SPEC.fmt, k_block=32)


def test_stale_schema_version_ignored(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({
        "version": CACHE_SCHEMA_VERSION + 1,
        "entries": {SPEC.key(): {"config": {
            "block_m": 8, "block_n": 8, "k_block": 8, "grouping": "nc"}}},
    }))
    cache = TuneCache.load(path)
    assert len(cache) == 0
    assert any("schema" in w for w in cache.load_warnings)


def test_malformed_entry_dropped_others_kept(tmp_path):
    path = tmp_path / "tune.json"
    good = BlockConfig(64, 64, 32, "nc")
    payload = {
        "version": CACHE_SCHEMA_VERSION,
        "entries": {
            "bad:key": {"config": {"block_m": "what"}},
            SPEC.key(): {**SPEC.to_json(), "config": good.to_json(),
                         "us": 1.0, "candidates_timed": 1},
        },
    }
    path.write_text(json.dumps(payload))
    cache = TuneCache.load(path)
    assert cache.get(SPEC.key()) == good
    assert "bad:key" not in cache.entries and cache.load_warnings


# ---------------------------------------------------------------------------
# resolution precedence: explicit > cache > default
# ---------------------------------------------------------------------------
def test_resolution_precedence(tmp_path):
    cache = TuneCache(tmp_path / "tune.json")
    cached = BlockConfig(32, 64, 64, "nc")
    cache.put(SPEC, cached, 1.0)
    # cache hit wins over default
    assert resolve_block_config(
        "gemm", SPEC.shape, SPEC.fmt, cache=cache) == cached
    # explicit fields win over the cached winner
    r = resolve_block_config(
        "gemm", SPEC.shape, SPEC.fmt, k_block=32, block_m=128, cache=cache)
    assert (r.block_m, r.block_n, r.k_block) == (128, 64, 32)
    # no hit -> proven-legal default at the caller's k_block
    r = resolve_block_config(
        "gemm", (8, 32, 8), SPEC.fmt, k_block=16, cache=cache)
    assert r == BlockConfig(128, 128, 16, "nc")


# ---------------------------------------------------------------------------
# committed seed cache: coverage + winners still prove legal
# ---------------------------------------------------------------------------
def test_seed_cache_exists_and_checks_clean():
    assert SEED_CACHE_PATH.exists(), (
        "committed seed cache missing; run "
        "python -m repro.kernels.autotune --tune --cache "
        "src/repro/kernels/tuned/kernel_tune.json")
    cache = TuneCache.load(SEED_CACHE_PATH)
    assert not cache.load_warnings
    report = check_cache(cache)
    assert report["ok"], report["failures"]
    # every registry tuning spec has a seeded winner
    for spec in registry_specs():
        assert cache.get(spec.key()) is not None, spec.key()


def test_check_cache_flags_missing_spec(tmp_path):
    report = check_cache(TuneCache(tmp_path / "empty.json"))
    assert not report["ok"]
    assert any("no tuning-cache entry" in f for f in report["failures"])


def test_check_cache_flags_illegal_winner(tmp_path):
    cache = TuneCache(tmp_path / "tune.json")
    # k_block=2048 at <2,4> overflows the 24-bit accumulator budget
    bad_spec = TuneSpec("gemm", (8, 2048, 8), FMT_IMAGENET, k_block=2048)
    cache.put(bad_spec, BlockConfig(8, 8, 2048, "nc"), 1.0)
    report = check_cache(cache, specs=[bad_spec])
    assert not report["ok"]
    assert any("no longer verifies" in f for f in report["failures"])


def test_quantize_spec_verifies():
    assert verify_config(QSPEC, BlockConfig(64, 128, 32, "nc")).ok


# ---------------------------------------------------------------------------
# process-wide interpret switch (REPRO_PALLAS_INTERPRET)
# ---------------------------------------------------------------------------
def test_interpret_env_switch(monkeypatch):
    monkeypatch.delenv(runtime.INTERPRET_ENV_VAR, raising=False)
    auto = runtime.default_interpret()
    assert isinstance(auto, bool)  # platform auto (True on CPU CI)
    monkeypatch.setenv(runtime.INTERPRET_ENV_VAR, "0")
    assert runtime.default_interpret() is False
    monkeypatch.setenv(runtime.INTERPRET_ENV_VAR, "off")
    assert runtime.default_interpret() is False
    monkeypatch.setenv(runtime.INTERPRET_ENV_VAR, "1")
    assert runtime.default_interpret() is True
    # explicit argument always wins
    assert runtime.resolve_interpret(False) is False
    monkeypatch.setenv(runtime.INTERPRET_ENV_VAR, "0")
    assert runtime.resolve_interpret(True) is True


def test_interpret_arg_defers_to_env(monkeypatch):
    monkeypatch.setenv(runtime.INTERPRET_ENV_VAR, "no")
    assert runtime.resolve_interpret(None) is False
    monkeypatch.setenv(runtime.INTERPRET_ENV_VAR, "yes")
    assert runtime.resolve_interpret(None) is True


# ---------------------------------------------------------------------------
# resolution reads committed files only, plus an explicit overlay
# ---------------------------------------------------------------------------
def test_resolution_ignores_uncommitted_local_cache(tmp_path, monkeypatch):
    """A stray ``.cache/kernel_tune.json`` in the working directory never
    changes resolution; ``REPRO_TUNE_CACHE`` overlays it explicitly."""
    from repro.kernels import autotune

    local = TuneCache(tmp_path / ".cache" / "kernel_tune.json")
    local.put(SPEC, BlockConfig(32, 32, 32), us=1.0)
    local.save()
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv(autotune.CACHE_ENV_VAR, raising=False)
    autotune.invalidate_cache()
    try:
        seed = TuneCache.load(SEED_CACHE_PATH)
        assert autotune.get_cache().entries == seed.entries
        assert autotune.get_cache().get(SPEC.key()) is None
        monkeypatch.setenv(autotune.CACHE_ENV_VAR, str(local.path))
        autotune.invalidate_cache()
        assert autotune.get_cache().get(SPEC.key()) == BlockConfig(32, 32, 32)
    finally:
        autotune.invalidate_cache()
