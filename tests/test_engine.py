"""SpMVEngine: plan-once/execute-many semantics, schedule-cache identity,
and bit-exact agreement with the per-call reference paths."""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (
    SpMVEngine,
    cached_block_schedule,
    clear_engine_cache,
    clear_schedule_cache,
    engine_cache_stats,
    get_engine,
    schedule_cache_stats,
    stream_digest,
)
from repro.core.formats import csr_to_sell, dense_to_csr
from repro.core.spmv import spmv_csr, spmv_sell, spmv_sell_coalesced

RNG = np.random.default_rng(42)


def _case(n_rows=100, n_cols=120, density=0.15, seed=0):
    rng = np.random.default_rng(seed)
    dense = rng.standard_normal((n_rows, n_cols)) * (
        rng.random((n_rows, n_cols)) < density
    )
    return dense, dense_to_csr(dense)


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_engine_cache()
    clear_schedule_cache()
    yield


@pytest.mark.parametrize("window,block_rows", [(16, 4), (64, 8), (256, 8)])
def test_matvec_matches_references(window, block_rows):
    dense, csr = _case()
    sell = csr_to_sell(csr)
    x = jnp.asarray(RNG.standard_normal(csr.n_cols).astype(np.float32))
    eng = SpMVEngine(sell, window=window, block_rows=block_rows)
    y = eng.matvec(x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(spmv_csr(csr, x)), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(spmv_sell(sell, x)), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(y), dense.astype(np.float32) @ np.asarray(x),
        rtol=2e-4, atol=2e-4,
    )


def test_engine_accepts_csr_input():
    dense, csr = _case(57, 91, seed=3)
    x = jnp.asarray(RNG.standard_normal(csr.n_cols).astype(np.float32))
    eng = SpMVEngine(csr, window=64, block_rows=8)
    np.testing.assert_allclose(
        np.asarray(eng.matvec(x)), dense.astype(np.float32) @ np.asarray(x),
        rtol=2e-4, atol=2e-4,
    )


def test_matmat_bit_identical_to_per_column_coalesced_spmv():
    """Acceptance: batched execution on a cached plan == per-column
    `spmv_sell_coalesced`, bit for bit."""
    _, csr = _case(80, 96, seed=7)
    sell = csr_to_sell(csr)
    X = jnp.asarray(RNG.standard_normal((csr.n_cols, 9)).astype(np.float32))
    eng = get_engine(sell, window=64, block_rows=8)
    Y = eng.matmat(X)
    assert Y.shape == (csr.n_rows, 9)
    for j in range(X.shape[1]):
        col = spmv_sell_coalesced(sell, X[:, j], window=64, block_rows=8)
        np.testing.assert_array_equal(np.asarray(Y[:, j]), np.asarray(col))


def test_matvec_matmat_consistency_and_shape_checks():
    _, csr = _case(40, 50, seed=11)
    eng = SpMVEngine(csr_to_sell(csr), window=32, block_rows=4)
    X = jnp.asarray(RNG.standard_normal((csr.n_cols, 3)).astype(np.float32))
    Y = eng.matmat(X)
    for j in range(3):
        np.testing.assert_array_equal(
            np.asarray(Y[:, j]), np.asarray(eng.matvec(X[:, j]))
        )
    with pytest.raises(ValueError):
        eng.matvec(jnp.zeros((csr.n_cols + 1,), jnp.float32))
    with pytest.raises(ValueError):
        eng.matmat(jnp.zeros((csr.n_cols + 1, 2), jnp.float32))
    # __call__ dispatches on rank
    np.testing.assert_array_equal(
        np.asarray(eng(X[:, 0])), np.asarray(eng.matvec(X[:, 0]))
    )
    np.testing.assert_array_equal(np.asarray(eng(X)), np.asarray(Y))


def test_schedule_cache_identity_and_keying():
    """Repeat plans return the *identical* schedule object; changing window
    or block_rows yields a distinct schedule."""
    _, csr = _case(60, 60, seed=5)
    sell = csr_to_sell(csr)
    a = SpMVEngine(sell, window=64, block_rows=8)
    b = SpMVEngine(sell, window=64, block_rows=8)
    sa = a.schedule  # planned first: cache miss
    sb = b.schedule  # repeat plan: content-addressed hit
    assert sb is sa
    assert a.plan_cached is False and b.plan_cached is True
    c = SpMVEngine(sell, window=32, block_rows=8)
    d = SpMVEngine(sell, window=64, block_rows=4)
    assert c.schedule is not a.schedule
    assert d.schedule is not a.schedule
    stats = schedule_cache_stats()
    assert stats["hits"] >= 1 and stats["misses"] >= 3


def test_cached_block_schedule_content_addressing():
    idx = np.arange(500, dtype=np.int32) % 97
    s1, hit1 = cached_block_schedule(idx, window=64, block_rows=8)
    s2, hit2 = cached_block_schedule(idx.copy(), window=64, block_rows=8)
    assert not hit1 and hit2  # different buffers, same content -> same plan
    assert s2 is s1
    s3, hit3 = cached_block_schedule(idx + 1, window=64, block_rows=8)
    assert not hit3 and s3 is not s1
    assert stream_digest(idx) == stream_digest(idx.copy())
    assert stream_digest(idx) != stream_digest(idx.astype(np.int64))


def test_get_engine_reuses_engine_and_compiled_fns():
    _, csr = _case(64, 64, seed=9)
    sell = csr_to_sell(csr)
    e1 = get_engine(sell, window=64, block_rows=8)
    x = jnp.asarray(RNG.standard_normal(csr.n_cols).astype(np.float32))
    e1.matvec(x)
    e2 = get_engine(sell, window=64, block_rows=8)
    assert e2 is e1
    assert engine_cache_stats()["hits"] >= 1
    # engine from the equivalent CSR content resolves to the same plan params
    e3 = get_engine(sell, window=32, block_rows=8)
    assert e3 is not e1


def test_get_engine_window_spellings_share_one_engine():
    """Regression: the engine cache must key on the *resolved* window, so
    `window=None` and its explicit spelling land on the same engine (object
    identity — no duplicate schedules, no duplicate jit compiles)."""
    _, csr = _case(64, 64, seed=25)
    sell = csr_to_sell(csr, slice_height=8)
    # reference: None resolves to DEFAULT_WINDOW = 256
    e_none = get_engine(sell, backend="reference")
    e_256 = get_engine(sell, backend="reference", window=256)
    assert e_256 is e_none
    # pallas: None resolves to cols_per_chunk * slice_height
    p_none = get_engine(sell, backend="pallas", cols_per_chunk=4)
    p_expl = get_engine(sell, backend="pallas", cols_per_chunk=4, window=32)
    assert p_expl is p_none
    assert p_none is not e_none
    stats = engine_cache_stats()
    assert stats["size"] == 2 and stats["hits"] >= 2
    # a window that fights the pallas geometry raises even when a matching
    # engine is already cached (resolution happens before the lookup)
    with pytest.raises(ValueError, match="window"):
        get_engine(sell, backend="pallas", cols_per_chunk=4, window=256)


def test_memory_hit_writes_through_to_disk_store(tmp_path):
    """Regression: a plan built *before* a cache directory was configured
    must reach the persistent store on a later in-memory hit that carries
    one — direct `cached_block_schedule` callers would otherwise never
    persist (the memory hit returned before the store was consulted)."""
    idx = (np.arange(700, dtype=np.int32) * 3) % 509
    s1, hit1 = cached_block_schedule(idx, window=64, block_rows=8)
    assert not hit1
    assert schedule_cache_stats()["disk_saves"] == 0
    assert list(tmp_path.iterdir()) == []
    s2, hit2 = cached_block_schedule(
        idx, window=64, block_rows=8, cache_dir=str(tmp_path)
    )
    assert hit2 and s2 is s1
    stats = schedule_cache_stats()
    assert stats["disk_saves"] == 1
    files = list(tmp_path.iterdir())
    assert len(files) == 1 and files[0].name.startswith("sched-")
    # the write-through is idempotent: the file exists now, no second save
    cached_block_schedule(idx, window=64, block_rows=8,
                          cache_dir=str(tmp_path))
    assert schedule_cache_stats()["disk_saves"] == 1
    # ...and a cold process (empty memory cache) loads it instead of planning
    clear_schedule_cache()
    s3, hit3 = cached_block_schedule(
        idx, window=64, block_rows=8, cache_dir=str(tmp_path)
    )
    stats = schedule_cache_stats()
    assert hit3 and stats["built"] == 0 and stats["disk_hits"] == 1
    np.testing.assert_array_equal(np.asarray(s3.tags), np.asarray(s1.tags))


def test_concurrent_get_engine_returns_one_engine():
    """Thread-safety smoke: N threads racing `get_engine` + matvec on the
    same matrix must observe a single engine object and produce identical
    results (the engine/schedule caches and plan counters are shared
    mutable state on the serving path)."""
    _, csr = _case(64, 80, seed=29)
    sell = csr_to_sell(csr)
    x = jnp.asarray(RNG.standard_normal(csr.n_cols).astype(np.float32))
    engines, results, errors = [], [], []
    barrier = threading.Barrier(8)

    def worker():
        try:
            barrier.wait(timeout=30)
            eng = get_engine(sell, window=64, block_rows=8,
                             backend="reference")
            engines.append(eng)
            results.append(np.asarray(eng.matvec(x)))
        except Exception as e:  # pragma: no cover - surfaced by the assert
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert len(engines) == 8
    assert all(e is engines[0] for e in engines)
    for r in results[1:]:
        np.testing.assert_array_equal(r, results[0])
    # one plan, one schedule — nothing was raced into duplicate existence
    assert schedule_cache_stats()["built"] == 1
    assert engine_cache_stats()["size"] == 1


def test_plan_report_contents():
    _, csr = _case(70, 70, seed=13)
    eng = SpMVEngine(csr_to_sell(csr), window=64, block_rows=8)
    rep = eng.plan_report()
    assert rep["n_rows"] == 70 and rep["n_cols"] == 70
    assert rep["window"] == 64 and rep["block_rows"] == 8
    # the default backend is "auto"; off-TPU it resolves to the reference
    # executor with no plan-level width padding
    assert rep["backend"] == "auto"
    assert rep["backend_resolved"] in ("reference", "pallas")
    if rep["backend_resolved"] == "reference":
        assert rep["plan_width"] == rep["padded_width"]
    assert rep["wide_accesses"] > 0
    assert 0 < rep["coalesce_rate"]
    assert rep["n_windows"] == eng.schedule.n_windows
    assert set(rep["perf"]) == {"base", "pack0", "pack256"}
    for r in rep["perf"].values():
        assert r["cycles"] > 0 and 0 < r["mem_utilization"] <= 1.0
    # pack256 should beat the coupled baseline on the model
    assert rep["perf"]["pack256"]["cycles"] < rep["perf"]["base"]["cycles"]


def test_sell_input_rejects_mismatched_conversion_params():
    """slice_height/width_multiple only steer CSR->SELL conversion; asking an
    already-built SELL for different geometry must raise, not be ignored."""
    _, csr = _case(50, 50, seed=19)
    sell = csr_to_sell(csr, slice_height=32)
    with pytest.raises(ValueError, match="slice_height"):
        SpMVEngine(sell, slice_height=4)
    with pytest.raises(ValueError, match="slice_height"):
        get_engine(sell, slice_height=4)
    with pytest.raises(ValueError, match="multiples"):
        get_engine(sell, width_multiple=64)
    # matching params are fine
    SpMVEngine(sell, slice_height=32, width_multiple=1)


def test_lazy_planning_perf_does_not_build_schedule():
    _, csr = _case(50, 50, seed=17)
    eng = SpMVEngine(csr_to_sell(csr), window=64, block_rows=8)
    assert eng._schedule is None
    eng.perf("pack256")
    assert eng._schedule is None  # perf-model query never pays for planning
    eng.matvec(jnp.zeros((csr.n_cols,), jnp.float32))
    assert eng._schedule is not None


def _resident_sell():
    """float32 SELL whose pallas plan holds x in VMEM: slice height 32,
    window 8 * 32 = 256, two lane rows."""
    _, csr = _case(96, 140, seed=31)
    return csr_to_sell(csr, slice_height=32)


@pytest.mark.parametrize("case", ["f32", "bf16", "x_over_budget",
                                  "reference", "explicit"])
def test_block_rows_none_resolves_to_a_lane_row_on_the_resident_path(
        case, monkeypatch):
    """`block_rows=None` coalesces at a whole 128-lane row where the pallas
    matvec will hold x in VMEM, and at 8 where it will not: 16-bit values,
    x over the budget, the reference backend. An explicit value wins."""
    from repro.kernels import sell_spmv

    sell = _resident_sell()
    kw = {"backend": "reference" if case == "reference" else "pallas"}
    if case == "bf16":
        kw["value_dtype"] = "bf16"
    if case == "explicit":
        kw["block_rows"] = 16
    if case == "x_over_budget":
        monkeypatch.setattr(sell_spmv, "X_RESIDENT_BUDGET", 64)
    expected = {"f32": 128, "explicit": 16}.get(case, 8)
    assert SpMVEngine(sell, **kw).block_rows == expected
    assert get_engine(sell, **kw).block_rows == expected


def test_default_and_lane_row_block_rows_share_one_engine():
    sell = _resident_sell()
    e_none = get_engine(sell, backend="pallas")
    e_128 = get_engine(sell, backend="pallas", block_rows=128)
    assert e_128 is e_none
    assert e_none.schedule.block_rows == 128
    assert schedule_cache_stats()["built"] == 1
    assert get_engine(sell, backend="pallas", block_rows=8) is not e_none


@pytest.mark.parametrize("value_dtype,expected", [(None, 128), ("bf16", 8)])
def test_sharded_engine_resolves_block_rows_once_for_every_shard(
        value_dtype, expected):
    from repro.core.dist import ShardedSpMVEngine

    sharded = ShardedSpMVEngine(_resident_sell(), backend="pallas",
                                n_shards=2, value_dtype=value_dtype)
    assert sharded.block_rows == expected
    assert [e.block_rows for e in sharded.engines] == [expected] * 2
