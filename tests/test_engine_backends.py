"""Execution backends for SpMVEngine: pallas-vs-reference parity, the
width-aware planner (pad/replan so W % cols_per_chunk == 0), and the
persistent schedule cache (cold process, warm disk -> zero plans built)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core import engine as engine_mod
from repro.core.engine import (
    SpMVEngine,
    clear_engine_cache,
    clear_schedule_cache,
    get_engine,
    resolve_backend,
    schedule_cache_stats,
)
from repro.core.formats import SELLMatrix, csr_to_sell, dense_to_csr

RNG = np.random.default_rng(21)


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_engine_cache()
    clear_schedule_cache()
    yield


def _sell_case(n_rows, n_cols, density, slice_height, seed, force_width=None):
    """Random SELL matrix; `force_width` pins the max slice width (so tests
    can guarantee W % cols_per_chunk != 0 coverage deterministically)."""
    rng = np.random.default_rng(seed)
    if force_width is None:
        dense = rng.standard_normal((n_rows, n_cols)) * (
            rng.random((n_rows, n_cols)) < density
        )
    else:
        dense = np.zeros((n_rows, n_cols))
        for r in range(n_rows):
            k = force_width if r == 0 else int(rng.integers(1, force_width + 1))
            cols = rng.choice(n_cols, size=k, replace=False)
            dense[r, cols] = rng.standard_normal(k)
    return dense, csr_to_sell(dense_to_csr(dense), slice_height=slice_height)


# Small enough for interpret-mode pallas, varied enough to cover even/odd
# widths and the default 32-row slice height.
GOLDEN_CASES = [
    dict(n_rows=64, n_cols=96, density=0.12, slice_height=32, seed=0),
    dict(n_rows=70, n_cols=90, density=0.13, slice_height=16, seed=1),
    dict(n_rows=33, n_cols=80, density=0.2, slice_height=8, seed=2,
         force_width=13),  # W = 13: not a multiple of any cpc used below
    dict(n_rows=48, n_cols=48, density=0.3, slice_height=8, seed=3),
]


def test_pallas_backend_matches_reference_on_golden_matrices():
    """Acceptance: backend="pallas" runs the sell_spmv kernel (interpret mode
    on CPU) and agrees with the reference backend to 1e-5 everywhere."""
    for case in GOLDEN_CASES:
        dense, sell = _sell_case(**case)
        x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
        ref = SpMVEngine(sell, backend="reference")
        pal = SpMVEngine(sell, backend="pallas", cols_per_chunk=4)
        y_ref = np.asarray(ref.matvec(x))
        y_pal = np.asarray(pal.matvec(x))
        assert np.abs(y_pal - y_ref).max() <= 1e-5, case
        np.testing.assert_allclose(
            y_pal, dense.astype(np.float32) @ np.asarray(x),
            rtol=2e-4, atol=2e-4,
        )
        rep = pal.plan_report()
        assert rep["backend_resolved"] == "pallas"
        assert rep["plan_width"] % pal.cols_per_chunk == 0
        assert rep["window"] == pal.cols_per_chunk * sell.slice_height


def test_pallas_plan_pads_width_when_not_a_multiple():
    _, sell = _sell_case(33, 80, 0.2, 8, seed=2, force_width=13)
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4)
    _, _, stream, W, W_plan = eng._ensure_plan()
    assert W == 13 and W_plan == 16  # replanned to the next multiple
    assert stream.size == sell.n_slices * W_plan * sell.slice_height
    # and the schedule is built against the padded geometry
    assert eng.schedule.n_windows * eng.window == stream.size


def test_pallas_matmat_matches_per_column_matvec():
    _, sell = _sell_case(40, 64, 0.15, 8, seed=5)
    X = jnp.asarray(RNG.standard_normal((sell.n_cols, 4)).astype(np.float32))
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4)
    Y = np.asarray(eng.matmat(X))
    assert Y.shape == (sell.n_rows, 4)
    for j in range(4):
        np.testing.assert_allclose(
            Y[:, j], np.asarray(eng.matvec(X[:, j])), rtol=1e-6, atol=1e-6
        )


@settings(max_examples=12, deadline=None)
@given(
    n_rows=st.integers(4, 80),
    n_cols=st.integers(8, 120),
    slice_height=st.sampled_from([8, 16]),
    cols_per_chunk=st.sampled_from([2, 4, 8]),
    density=st.floats(0.05, 0.35),
    seed=st.integers(0, 2**31 - 1),
)
def test_width_aware_replanning_is_bit_identical(
    n_rows, n_cols, slice_height, cols_per_chunk, density, seed
):
    """The width-padded plan (the geometry the pallas backend executes) must
    be numerically invisible: executing it with the reference executor gives
    the *bit-identical* result of the plain reference backend — the padded
    schedule gathers exactly the same elements for every real column. Draws
    cover W % cols_per_chunk != 0 (odd widths) and == 0 (no-op padding)."""
    _, sell = _sell_case(n_rows, n_cols, density, slice_height, seed)
    x = jnp.asarray(
        np.random.default_rng(seed + 1).standard_normal(sell.n_cols)
        .astype(np.float32)
    )
    window = cols_per_chunk * slice_height
    plain = SpMVEngine(sell, window=window, backend="reference")
    padded = SpMVEngine(
        sell, window=window, backend="reference",
        plan_width_multiple=cols_per_chunk,
    )
    np.testing.assert_array_equal(
        np.asarray(plain.matvec(x)), np.asarray(padded.matvec(x))
    )


def test_width_aware_replanning_bit_identical_on_odd_width():
    """Deterministic W % cols_per_chunk != 0 instance of the property above
    (the random draws *usually* hit one, this always does)."""
    _, sell = _sell_case(33, 80, 0.2, 8, seed=2, force_width=13)
    x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
    plain = SpMVEngine(sell, window=64, backend="reference")
    padded = SpMVEngine(
        sell, window=64, backend="reference", plan_width_multiple=8
    )
    assert padded._ensure_plan()[4] != padded._ensure_plan()[3]  # real pad
    np.testing.assert_array_equal(
        np.asarray(plain.matvec(x)), np.asarray(padded.matvec(x))
    )


def test_auto_backend_resolves_off_tpu():
    assert resolve_backend("auto") == (
        "pallas" if jax.default_backend() == "tpu" else "reference"
    )
    _, sell = _sell_case(32, 32, 0.2, 8, seed=7)
    eng = SpMVEngine(sell, backend="auto")
    assert eng.backend == "auto"
    assert eng.backend_resolved == resolve_backend("auto")


def test_repro_backend_env_var_steers_auto(monkeypatch):
    """$REPRO_BACKEND overrides the platform rule for backend="auto" only:
    explicit backends win, empty string means unset, garbage is
    rejected."""
    platform_default = resolve_backend("auto")
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    assert resolve_backend("auto") == "pallas"
    assert resolve_backend("reference") == "reference"  # explicit wins
    _, sell = _sell_case(32, 32, 0.2, 8, seed=7)
    eng = SpMVEngine(sell, backend="auto", cols_per_chunk=4)
    assert eng.backend_resolved == "pallas"
    monkeypatch.setenv("REPRO_BACKEND", "reference")
    assert resolve_backend("auto") == "reference"
    assert resolve_backend("pallas") == "pallas"
    monkeypatch.setenv("REPRO_BACKEND", "")
    assert resolve_backend("auto") == platform_default  # empty = unset
    monkeypatch.setenv("REPRO_BACKEND", "auto")
    assert resolve_backend("auto") == platform_default
    monkeypatch.setenv("REPRO_BACKEND", "cuda")
    with pytest.raises(ValueError, match="REPRO_BACKEND"):
        resolve_backend("auto")


def test_invalid_backend_and_window_mismatch_raise():
    _, sell = _sell_case(32, 32, 0.2, 8, seed=7)
    with pytest.raises(ValueError, match="backend"):
        SpMVEngine(sell, backend="cuda")
    # pallas windows are structurally cols_per_chunk * slice_height = 64 here
    with pytest.raises(ValueError, match="window"):
        SpMVEngine(sell, backend="pallas", cols_per_chunk=8, window=32)
    # matching explicit window is accepted
    SpMVEngine(sell, backend="pallas", cols_per_chunk=8, window=64)


def test_get_engine_keys_on_resolved_backend():
    _, sell = _sell_case(32, 32, 0.2, 8, seed=9)
    ref = get_engine(sell, backend="reference")
    pal = get_engine(sell, backend="pallas", cols_per_chunk=4)
    assert ref is not pal
    assert get_engine(sell, backend="reference") is ref
    assert get_engine(sell, backend="pallas", cols_per_chunk=4) is pal
    # reference engines ignore cols_per_chunk in the key (it only shapes
    # pallas plans)
    assert get_engine(sell, backend="reference", cols_per_chunk=4) is ref


# ---------------------------------------------------------------------------
# Persistent schedule cache through the engine
# ---------------------------------------------------------------------------


def test_cold_process_with_warm_disk_cache_builds_zero_schedules(
    tmp_path, monkeypatch
):
    """Acceptance: warm on-disk cache -> zero build_block_schedule calls in a
    fresh process (simulated by clearing every in-memory cache and making
    plan construction raise)."""
    _, sell = _sell_case(48, 64, 0.15, 8, seed=11)
    x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
    cache_dir = str(tmp_path)

    e1 = SpMVEngine(sell, backend="reference", cache_dir=cache_dir)
    y1 = np.asarray(e1.matvec(x))
    stats = schedule_cache_stats()
    assert stats["built"] == 1 and stats["disk_saves"] == 1

    clear_engine_cache()
    clear_schedule_cache()

    def _forbidden(*a, **k):
        raise AssertionError("cold process replanned despite warm disk cache")

    monkeypatch.setattr(engine_mod, "build_block_schedule", _forbidden)
    e2 = SpMVEngine(sell, backend="reference", cache_dir=cache_dir)
    y2 = np.asarray(e2.matvec(x))
    stats = schedule_cache_stats()
    assert stats["built"] == 0 and stats["disk_hits"] == 1
    assert e2.plan_cached is True  # a disk hit is a cache hit
    np.testing.assert_array_equal(y1, y2)


def test_cache_dir_defaults_to_env_var(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path))
    _, sell = _sell_case(32, 48, 0.2, 8, seed=13)
    eng = SpMVEngine(sell, backend="reference")
    assert eng.cache_dir == str(tmp_path)
    eng.matvec(jnp.zeros((sell.n_cols,), jnp.float32))
    assert schedule_cache_stats()["disk_saves"] == 1
    assert any(p.name.startswith("sched-") for p in tmp_path.iterdir())


def test_stream_sharing_matrices_get_independent_persisted_plans(tmp_path):
    """Two matrices can share a column-index stream (same sparsity, different
    values). Each persists under its own matrix-digest-keyed file — both stay
    disk-warm, neither rejects or overwrites the other's plan."""
    _, sell_a = _sell_case(48, 64, 0.15, 8, seed=17)
    sell_b = SELLMatrix(
        n_rows=sell_a.n_rows,
        n_cols=sell_a.n_cols,
        slice_height=sell_a.slice_height,
        slice_ptrs=sell_a.slice_ptrs,
        slice_widths=sell_a.slice_widths,
        colidx=sell_a.colidx,  # identical index stream
        values=sell_a.values * 2.0,  # different content
    )
    x = jnp.asarray(RNG.standard_normal(sell_a.n_cols).astype(np.float32))
    cache_dir = str(tmp_path)

    SpMVEngine(sell_a, backend="reference", cache_dir=cache_dir).matvec(x)
    clear_engine_cache()
    clear_schedule_cache()
    y_b = np.asarray(
        SpMVEngine(sell_b, backend="reference", cache_dir=cache_dir).matvec(x)
    )
    assert len(list(tmp_path.iterdir())) == 2  # one file per matrix
    np.testing.assert_allclose(
        y_b,
        2.0 * np.asarray(SpMVEngine(sell_a, backend="reference").matvec(x)),
        rtol=1e-5, atol=1e-5,
    )
    # ...and now both cold-start warm. (A's disk load fills the in-memory
    # content-addressed cache; B's byte-identical stream hits *that*, so one
    # disk read serves both — and nothing is ever rebuilt or rejected.)
    clear_engine_cache()
    clear_schedule_cache()
    SpMVEngine(sell_a, backend="reference", cache_dir=cache_dir).matvec(x)
    SpMVEngine(sell_b, backend="reference", cache_dir=cache_dir).matvec(x)
    stats = schedule_cache_stats()
    assert stats["built"] == 0 and stats["disk_rejects"] == 0
    assert stats["disk_hits"] == 1 and stats["hits"] >= 1


def test_tampered_matrix_digest_rejected_on_load(tmp_path):
    """A persisted file whose header names a different matrix than the one
    looking it up is rejected and the plan rebuilt (defense against moved,
    tampered, or hash-colliding files)."""
    from repro.core import schedule_store
    from repro.core.coalescer import build_block_schedule
    from repro.core.engine import _sell_content_digest, stream_digest

    _, sell = _sell_case(48, 64, 0.15, 8, seed=17)
    eng = SpMVEngine(sell, backend="reference", cache_dir=str(tmp_path))
    _, _, stream, _, _ = eng._ensure_plan()
    digest = stream_digest(stream)
    # Plant a valid schedule at exactly the path this engine will probe, but
    # attributed to some other matrix.
    path = schedule_store.schedule_path(
        str(tmp_path), digest, window=eng.window, block_rows=eng.block_rows,
        matrix_digest=_sell_content_digest(sell),
    )
    sched = build_block_schedule(
        stream, window=eng.window, block_rows=eng.block_rows
    )
    schedule_store.save_schedule(
        path, sched, stream_digest=digest, matrix_digest="0" * 64
    )
    eng.matvec(jnp.zeros((sell.n_cols,), jnp.float32))
    stats = schedule_cache_stats()
    assert stats["disk_rejects"] == 1 and stats["built"] == 1


def test_get_engine_adopts_cache_dir_on_cache_hit(tmp_path):
    """An explicit cache_dir on a get_engine hit must not be silently
    dropped: the engine adopts the directory and writes through a plan that
    was already built without persistence."""
    _, sell = _sell_case(48, 64, 0.15, 8, seed=23)
    x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
    e1 = get_engine(sell, backend="reference")  # no persistence
    e1.matvec(x)
    assert schedule_cache_stats()["disk_saves"] == 0
    e2 = get_engine(sell, backend="reference", cache_dir=str(tmp_path))
    assert e2 is e1 and e2.cache_dir == str(tmp_path)
    assert schedule_cache_stats()["disk_saves"] == 1  # written through
    clear_engine_cache()
    clear_schedule_cache()
    get_engine(sell, backend="reference", cache_dir=str(tmp_path)).matvec(x)
    stats = schedule_cache_stats()
    assert stats["built"] == 0 and stats["disk_hits"] == 1


def test_pallas_engine_persists_and_reloads_its_padded_plan(tmp_path):
    """Persistence composes with the width-aware planner: the pallas engine's
    padded-geometry schedule round-trips through disk and still matches the
    reference backend."""
    _, sell = _sell_case(33, 80, 0.2, 8, seed=2, force_width=13)
    x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
    cache_dir = str(tmp_path)
    e1 = SpMVEngine(sell, backend="pallas", cols_per_chunk=4,
                    cache_dir=cache_dir)
    y1 = np.asarray(e1.matvec(x))
    clear_engine_cache()
    clear_schedule_cache()
    e2 = SpMVEngine(sell, backend="pallas", cols_per_chunk=4,
                    cache_dir=cache_dir)
    y2 = np.asarray(e2.matvec(x))
    stats = schedule_cache_stats()
    assert stats["built"] == 0 and stats["disk_hits"] == 1
    np.testing.assert_array_equal(y1, y2)
    y_ref = np.asarray(
        SpMVEngine(sell, backend="reference").matvec(x)
    )
    assert np.abs(y2 - y_ref).max() <= 1e-5


def test_schedule_trimming_shrinks_warp_dimension():
    """cached_block_schedule trims the tag matrix to the warps the stream
    actually uses — the lever that keeps interpret-mode pallas grids small."""
    _, sell = _sell_case(64, 64, 0.15, 8, seed=19)
    eng = SpMVEngine(sell, window=64, backend="reference")
    sched = eng.schedule
    n_warps = np.asarray(sched.n_warps)
    assert sched.max_warps == max(int(n_warps.max()), 1)
    assert sched.max_warps < 64  # strictly below the always-safe bound
