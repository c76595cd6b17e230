"""Plan autotuner: deterministic model-mode search, the persistent winner
cache (cold process + warm tune cache runs zero trials), tamper rejection,
and the get_engine handoff."""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import tune as tune_mod
from repro.core.engine import clear_engine_cache, clear_schedule_cache
from repro.core.formats import csr_to_sell
from repro.core.matrices import banded
from repro.core.tune import (
    DEFAULT_SPACE,
    autotune,
    clear_tune_cache,
    get_tuned_engine,
    resolve_tune_cache_dir,
    tune_key,
    tune_path,
    tune_stats,
)

SELL = csr_to_sell(banded(256, 12, 0.7)(np.random.default_rng(0)))
N_CANDIDATES = 36  # |DEFAULT_SPACE| = 3 * 3 * 2 * 2


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_tune_cache()
    clear_engine_cache()
    clear_schedule_cache()
    yield


def test_model_mode_search_is_deterministic_and_in_space():
    p1 = autotune(SELL, k=32, backend="reference", mode="model")
    assert p1.cols_per_chunk in DEFAULT_SPACE["cols_per_chunk"]
    assert p1.block_rows in DEFAULT_SPACE["block_rows"]
    assert p1.packed in DEFAULT_SPACE["packed"]
    assert p1.source == "search" and p1.trials == N_CANDIDATES
    assert p1.cost > 0
    clear_tune_cache()
    p2 = autotune(SELL, k=32, backend="reference", mode="model")
    assert (p2.cols_per_chunk, p2.block_rows, p2.packed, p2.cost) == (
        p1.cols_per_chunk, p1.block_rows, p1.packed, p1.cost
    )


def test_memory_cache_hit_runs_zero_trials():
    p1 = autotune(SELL, k=16, backend="reference", mode="model")
    assert p1.trials == N_CANDIDATES
    p2 = autotune(SELL, k=16, backend="reference", mode="model")
    assert p2.source == "memory" and p2.trials == 0
    assert (p2.cols_per_chunk, p2.block_rows, p2.packed) == (
        p1.cols_per_chunk, p1.block_rows, p1.packed
    )
    stats = tune_stats()
    assert stats["searched"] == 1 and stats["memory_hits"] == 1


def test_tune_cache_roundtrip_cold_process_runs_zero_trials(
    tmp_path, monkeypatch
):
    """Acceptance: warm on-disk tune cache -> zero candidate evaluations in
    a fresh process (simulated by clearing the in-memory cache and making
    the search paths raise)."""
    cache_dir = str(tmp_path)
    p1 = autotune(SELL, k=32, backend="reference", mode="model",
                  cache_dir=cache_dir)
    assert p1.trials == N_CANDIDATES
    assert tune_stats()["disk_saves"] == 1
    assert any(f.name.startswith("tune-") for f in tmp_path.iterdir())

    clear_tune_cache()

    def _forbidden(*a, **k):
        raise AssertionError("cold process re-searched despite a warm "
                             "tune cache")

    monkeypatch.setattr(tune_mod, "_model_search", _forbidden)
    monkeypatch.setattr(tune_mod, "_measure_search", _forbidden)
    p2 = autotune(SELL, k=32, backend="reference", mode="model",
                  cache_dir=cache_dir)
    assert p2.source == "disk" and p2.trials == 0
    assert (p2.cols_per_chunk, p2.block_rows, p2.packed) == (
        p1.cols_per_chunk, p1.block_rows, p1.packed
    )
    stats = tune_stats()
    assert stats["searched"] == 0 and stats["disk_hits"] == 1
    # ...and the disk hit filled the in-memory cache for the process's life
    p3 = autotune(SELL, k=32, backend="reference", mode="model",
                  cache_dir=cache_dir)
    assert p3.source == "memory" and p3.trials == 0


def test_distinct_questions_get_distinct_winner_files(tmp_path):
    cache_dir = str(tmp_path)
    autotune(SELL, k=8, backend="reference", mode="model",
             cache_dir=cache_dir)
    autotune(SELL, k=64, backend="reference", mode="model",
             cache_dir=cache_dir)
    assert len(list(tmp_path.iterdir())) == 2  # k is part of the identity


def test_custom_hw_config_gets_its_own_winner():
    """The hardware model is part of the search identity: a custom HWConfig
    must re-search, not hit the DEFAULT_HW winner with zero trials."""
    from repro.core.perfmodel import DEFAULT_HW

    p_default = autotune(SELL, k=16, backend="reference", mode="model")
    assert p_default.trials == N_CANDIDATES
    slow_channel = dataclasses.replace(
        DEFAULT_HW, channel_bytes_per_cycle=4.0
    )
    p_custom = autotune(SELL, k=16, backend="reference", mode="model",
                        hw=slow_channel)
    assert p_custom.source == "search" and p_custom.trials == N_CANDIDATES
    assert p_custom.cost != p_default.cost  # scored under the custom model


def test_winner_body_outside_space_rejected(tmp_path):
    """A winner file whose body smuggles knobs the keyed search space never
    produced is rejected even with an intact header."""
    cache_dir = str(tmp_path)
    autotune(SELL, k=32, backend="reference", mode="model",
             cache_dir=cache_dir)
    path = next(tmp_path.iterdir())
    payload = json.loads(path.read_text())
    payload["winner"]["block_rows"] = 999  # not in DEFAULT_SPACE
    path.write_text(json.dumps(payload))
    clear_tune_cache()
    p = autotune(SELL, k=32, backend="reference", mode="model",
                 cache_dir=cache_dir)
    stats = tune_stats()
    assert stats["disk_rejects"] == 1 and stats["searched"] == 1
    assert p.source == "search"
    assert p.block_rows in DEFAULT_SPACE["block_rows"]


def test_tampered_winner_file_rejected_and_researched(tmp_path):
    cache_dir = str(tmp_path)
    p1 = autotune(SELL, k=32, backend="reference", mode="model",
                  cache_dir=cache_dir)
    path = next(tmp_path.iterdir())
    payload = json.loads(path.read_text())
    payload["matrix_digest"] = "0" * 64  # some other matrix's winner
    path.write_text(json.dumps(payload))
    clear_tune_cache()
    p2 = autotune(SELL, k=32, backend="reference", mode="model",
                  cache_dir=cache_dir)
    stats = tune_stats()
    assert stats["disk_rejects"] == 1 and stats["searched"] == 1
    assert p2.source == "search" and p2.trials == N_CANDIDATES
    assert (p2.cols_per_chunk, p2.block_rows, p2.packed) == (
        p1.cols_per_chunk, p1.block_rows, p1.packed
    )


def test_cache_dir_env_var_and_schedule_store_fallback(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
    monkeypatch.delenv("REPRO_SCHEDULE_CACHE", raising=False)
    assert resolve_tune_cache_dir(None) is None
    monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path / "sched"))
    # no tune dir configured -> winners live next to the schedule store
    assert resolve_tune_cache_dir(None) == str(tmp_path / "sched")
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune"))
    assert resolve_tune_cache_dir(None) == str(tmp_path / "tune")
    assert resolve_tune_cache_dir(str(tmp_path / "x")) == str(tmp_path / "x")
    autotune(SELL, k=8, backend="reference", mode="model")
    assert any(
        f.name.startswith("tune-") for f in (tmp_path / "tune").iterdir()
    )


def test_measure_mode_reference_backend():
    plan = autotune(
        SELL, k=4, backend="reference", mode="measure",
        space={"cols_per_chunk": (8,), "block_rows": (4, 8),
               "packed": (1,), "value_dtype": ("native",)},
        rounds=2,
    )
    assert plan.source == "search" and plan.mode == "measure"
    assert plan.trials == 4  # 2 candidates x 2 interleaved rounds
    assert plan.block_rows in (4, 8) and plan.cost > 0


def test_space_validation():
    with pytest.raises(ValueError, match="unknown"):
        autotune(SELL, k=4, mode="model", space={"warp_size": (32,)})
    with pytest.raises(ValueError, match=">= 1"):
        autotune(SELL, k=4, mode="model", space={"block_rows": (0,)})
    with pytest.raises(ValueError, match="packed"):
        autotune(SELL, k=4, mode="model", space={"packed": (2,)})
    with pytest.raises(ValueError, match="mode"):
        autotune(SELL, k=4, mode="exhaustive")
    with pytest.raises(ValueError, match="k must be"):
        autotune(SELL, k=0, mode="model")


def test_get_tuned_engine_feeds_get_engine(tmp_path):
    engine, plan = get_tuned_engine(
        SELL, k=16, backend="reference", mode="model",
        tune_cache_dir=str(tmp_path),
    )
    assert engine.block_rows == plan.block_rows
    assert engine.packed == bool(plan.packed)
    # repeat call: warm tuner (disk/memory) + warm engine cache
    engine2, plan2 = get_tuned_engine(
        SELL, k=16, backend="reference", mode="model",
        tune_cache_dir=str(tmp_path),
    )
    assert engine2 is engine and plan2.trials == 0


def test_tuned_plan_key_stable_across_space_orderings():
    digest = "ab" * 32
    a = tune_key(digest, k=8, backend="pallas", mode="model",
                 space=tune_mod._normalize_space(
                     {"packed": (1, 0), "cols_per_chunk": (4, 8),
                      "block_rows": (8,)}))
    b = tune_key(digest, k=8, backend="pallas", mode="model",
                 space=tune_mod._normalize_space(
                     {"block_rows": (8,), "cols_per_chunk": (8, 4),
                      "packed": (0, 1)}))
    assert a == b
    assert tune_path("/tmp/cache", a).endswith(f"tune-{a}.json")
